"""CPU tests of the benchmark's own code (collected with the repo's suite)."""
