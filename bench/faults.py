"""Faults planted under the timed path, each where the program produces its
answers (``Int8Executor.__call__``): the check has to see ``correct`` come
out false under every one.  ``plant(fault)`` patches the executor and
returns the function that takes the fault out again."""
import numpy as np


def answer_altered(out: dict, x) -> dict:
    """Every served answer's classes shifted by one."""
    return {k: np.roll(np.asarray(v), 1, axis=-1) for k, v in out.items()}


def half_batch_left_out(out: dict, x) -> dict:
    """The second half of every batch left out: its rows repeat the first
    half's answers."""
    n = len(x)
    return {k: np.concatenate([np.asarray(v)[:(n + 1) // 2],
                               np.asarray(v)[:n // 2]])
            for k, v in out.items()}


def answers_shifted(out: dict, x) -> dict:
    """Every request of a batch gets the answer of the one beside it."""
    return {k: np.roll(np.asarray(v), 1, axis=0) for k, v in out.items()}


FAULTS = {f.__name__: f for f in (answer_altered, half_batch_left_out,
                                  answers_shifted)}


def plant(fault):
    from repro.core.executor import Int8Executor

    orig = Int8Executor.__call__
    Int8Executor.__call__ = lambda self, x: fault(orig(self, x), x)

    def undo():
        Int8Executor.__call__ = orig
    return undo
