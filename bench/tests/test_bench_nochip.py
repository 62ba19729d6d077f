"""Without a TPU the benchmark fails and prints no result."""
import json
import os
import subprocess
import sys

from bench import spec


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = spec.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         cell, "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "platform cpu" in p.stdout
    assert "device count" in p.stdout
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj
    assert "nothing was run" in p.stderr
