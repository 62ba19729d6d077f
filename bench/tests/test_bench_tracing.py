"""Trace reductions on hand-built event lists (seconds)."""
import pytest

from bench import tracing

# one program execution [0, 10]: two chain launches, a horizontal launch,
# a pad before them and a copy overlapping the second chain launch
OPS = [("pad.3", 0.0, 1.0), ("_run_chain.1", 1.0, 4.0),
       ("_run_horizontal.2", 4.0, 5.0), ("_run_chain.7", 6.0, 8.0),
       ("copy.4", 7.0, 9.0)]
MODULES = [("jit_fn", 0.0, 10.0), ("jit_fn", 11.0, 30.0)]


def test_busy_union_and_gaps():
    assert tracing.busy_s(OPS) == pytest.approx(8.0)     # [0,5] + [6,9]
    assert tracing.gaps(OPS, 0.0, 12.0) == [(5.0, 6.0), (9.0, 12.0)]
    idle = 1 - tracing.busy_s(tracing.clip(OPS, 0.0, 12.0)) / 12.0
    assert idle == pytest.approx(4.0 / 12.0)


def test_clip_cuts_events_to_the_window():
    assert tracing.clip(OPS, 3.0, 7.5) == [
        ("_run_chain.1", 3.0, 4.0), ("_run_horizontal.2", 4.0, 5.0),
        ("_run_chain.7", 6.0, 7.5), ("copy.4", 7.0, 7.5)]
    assert tracing.busy_s(tracing.clip(OPS, 3.0, 7.5)) == pytest.approx(3.5)


def test_kernel_and_outside_kernel_split():
    assert [tracing.kernel_kind(n) for n, _, _ in OPS] == [
        None, "chain", "horizontal", "chain", None]
    assert tracing.kernel_kind("_run_chainx") is None
    assert tracing.kernel_kind("_run_chain.26 s8[32,56,56,64]") == "chain"


def test_short_name_of_an_hlo_op_event():
    ev = ("%_run_chain.26 = s8[32,56,56,64]{3,2,1,0:T(8,128)(4,1)S(1)} "
          "custom-call(s8[32,231,231,3]{3,2,1,0} %pad.26), "
          'custom_call_target="tpu_custom_call"')
    assert tracing.short_name(ev) == "_run_chain.26 s8[32,56,56,64]"
    assert tracing.short_name("%copy = s8[32,224,224,3]{3,2,1,0} copy(%x)") \
        == "copy s8[32,224,224,3]"
    assert tracing.short_name("jit_fn(1136938)") == "jit_fn(1136938)"
    # busy 8; launches cover [1,5] + [6,8] = 6; pad 1 and the copy's
    # uncovered [8,9] are outside the kernels
    assert tracing.outside_kernel_s(OPS) == pytest.approx(2.0)


def test_complete_runs_keep_whole_executions_with_launches():
    runs = tracing.complete_runs(OPS, MODULES)
    assert len(runs) == 1                     # the second holds no launch
    assert tracing.kernel_time(runs, "chain") == pytest.approx(5.0)
    assert tracing.kernel_time(runs, "horizontal") == pytest.approx(1.0)


def test_top_ops_and_named_gaps():
    ops = OPS + [("_run_chain.1", 20.0, 21.0)]
    assert tracing.top_ops(ops, 2) == [["_run_chain.1", 4.0],
                                       ["_run_chain.7", 2.0]]
    named = tracing.named_gaps(OPS, MODULES, 0.0, 12.0)
    # [9, 12] crosses the end of the first program; [5, 6] lies inside it
    assert [g[1] for g in named] == [3.0, 1.0]
    assert named[0][0].startswith("between programs")
    assert named[1][0] == "in program, before _run_chain.7"


def test_program_period_from_the_starts_of_programs_with_launches():
    ops = [("_run_chain.1", 1.0, 2.0), ("copy.1", 12.5, 13.0),
           ("_run_chain.1", 21.0, 22.0), ("_run_chain.1", 31.0, 33.0)]
    mods = [("jit_fn", 30.5, 34.0), ("jit_fn", 0.5, 3.0),
            ("jit_fn", 12.0, 14.0), ("jit_fn", 20.5, 23.0),
            ("jit_fn", 40.0, 41.0)]
    # programs with launches start at 0.5, 20.5 and 30.5
    assert tracing.program_period_s(ops, mods) == pytest.approx(15.0)
    assert tracing.program_period_s(ops[:1], mods) is None
