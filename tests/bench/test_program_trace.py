"""What the program's own marks say about a window, on synthetic
intervals: launch gaps outside module executions, attributed to the
launcher's ``train.*`` spans; busy time by named scope; the per-round
metrics; the slowest round; the useful-share reader; and that scoped
operations leave the harness's reduction as it was."""
from __future__ import annotations

from collections import namedtuple

import pytest

import _bench_cells  # noqa: F401  (puts the repo root on the path)
from bench import harness, program_trace as P, trace as T
from bench.metrics import (round_device_ms, round_useful_share,
                           train_idle_share, train_mfu)


def ev(name, s, t):
    return T.Event(name, s, t)


def op(s, t, scope="", name="fusion"):
    return P.Op(name, s, t, scope)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(parallel_round)/while/body/closed_call/local_step/"
     "vmap(transpose(jvp(attention)))/dot_general", "attention"),
    ("jit(f)/while/body/local_step/vmap(jvp())/while/body/closed_call/"
     "checkpoint/mlp/dot_general", "mlp"),
    ("jit(f)/local_step/vmap(transpose(jvp(head)))/broadcast_in_dim",
     "head"),
    ("jit(f)/aggregate/reduce_sum", "aggregate"),
    ("jit(f)/while/body/optimizer/mul", "optimizer"),
    ("jit(f)/broadcast/broadcast_in_dim", "broadcast"),
    ("jit(f)/while/body/bhqk,bkhd->bqhd/dot_general", ""),
    ("", ""),
])
def test_innermost_scope_of_an_op_name(op_name, scope):
    assert P.scope_of(op_name) == scope


def test_intersect_of_disjoint_interval_lists():
    a = [(0.0, 2.0), (3.0, 5.0), (8.0, 9.0)]
    b = [(1.0, 4.0), (4.5, 8.5)]
    assert P.intersect(a, b) == [(1.0, 2.0), (3.0, 4.0), (4.5, 5.0),
                                 (8.0, 8.5)]
    assert P.intersect(a, []) == []


def test_launch_gaps_count_outside_modules_by_train_span():
    # device: two rounds' programs [1, 4] and [6, 9]; inside the first an
    # idle stretch 2-2.5 (the program waits on itself)
    ops = [op(1.0, 2.0), op(2.5, 4.0), op(6.0, 9.0)]
    modules = [ev("jit_round", 1.0, 4.0), ev("jit_round", 6.0, 9.0)]
    spans = [ev("train.round", 0.0, 5.0), ev("train.fetch", 3.0, 4.5),
             ev("train.round", 5.0, 10.0), ev("train.batch", 4.8, 5.8)]
    got = P.launch_gaps(ops, modules, spans, 0.0, 10.0)
    # outside the modules: 0-1 (midpoint in the first round), 4-6
    # (midpoint 5: the batch span is the innermost there), 9-10
    assert got == pytest.approx({"train.round": 1.0 + 1.0,
                                 "train.batch": 2.0,
                                 P.INSIDE: 0.5})
    idle = sum(t - s for s, t in T.idle_gaps(ops, 0.0, 10.0))
    assert sum(got.values()) == pytest.approx(idle)


def test_launch_gaps_outside_every_span_and_without_modules():
    ops = [op(1.0, 2.0)]
    got = P.launch_gaps(ops, [], [], 0.0, 3.0)
    assert got == pytest.approx({"outside any span": 2.0, P.INSIDE: 0.0})


def test_scope_busy_time_is_each_scopes_union():
    ops = [op(0.0, 2.0, "attention"), op(1.0, 3.0, "attention"),
           op(3.0, 4.0, "mlp"), op(5.0, 5.5), op(9.0, 12.0, "aggregate")]
    got = P.device_scopes(ops, 0.0, 10.0)
    assert got == pytest.approx({"attention": 3.0, "mlp": 1.0,
                                 P.UNSCOPED: 0.5, "aggregate": 1.0})


def _trace():
    return P.ProgramTrace(
        ops={"/device:TPU:0": [op(1.0, 3.0, "attention"),
                               op(3.0, 4.0, "aggregate"), op(6.0, 7.0)]},
        modules={"/device:TPU:0": [ev("jit_round", 1.0, 4.0),
                                   ev("jit_round", 6.0, 7.0)]},
        spans=[ev("train.dispatch", 0.0, 1.0), ev("train.fetch", 4.0, 6.0)],
        window=(0.0, 8.0))


def test_breakdown_and_per_round_metrics():
    brk = P.breakdown(_trace())
    assert dict(brk["launch_gaps"]) == pytest.approx({
        "train.dispatch": 1.0, "train.fetch": 2.0,
        "outside any span": 1.0, P.INSIDE: 0.0})
    assert dict(brk["device_scopes"]) == pytest.approx({
        "attention": 2.0, "aggregate": 1.0, P.UNSCOPED: 1.0})
    assert brk["device_scopes"][0][0] == "attention"
    assert brk["scope_ops"] == {"attention": [["fusion", 2.0]],
                                "aggregate": [["fusion", 1.0]],
                                P.UNSCOPED: [["fusion", 1.0]]}
    got = P.round_metrics(brk, rounds=2)
    assert got == pytest.approx({"round_launch_gap_ms": 2000.0,
                                 "round_attention_ms": 1000.0,
                                 "round_aggregate_ms": 500.0})
    assert P.round_metrics(brk, rounds=0) == {}
    brk["device_scopes"] = [[P.UNSCOPED, 4.0]]
    assert set(P.round_metrics(brk, rounds=2)) == {"round_launch_gap_ms"}


Rec = namedtuple("Rec", "name start end parent round")


def test_slowest_round_is_taken_apart_by_its_spans():
    recs = [Rec("train.round", 0.0, 1.0, None, 0),        # set-up round
            Rec("train.batch", 1.0, 1.1, "train.round", 1),
            Rec("train.fetch", 1.1, 1.2, "train.round", 1),
            Rec("train.round", 1.0, 1.2, None, 1),
            Rec("train.batch", 1.2, 1.3, "train.round", 2),
            Rec("train.gc", 1.35, 1.85, "train.fetch", 2),
            Rec("train.fetch", 1.3, 1.9, "train.round", 2),
            Rec("train.round", 1.2, 1.9, None, 2)]
    got = P.slowest_round(recs, rounds=2)
    assert got["round"] == 2
    assert got["ms"] == pytest.approx(700.0)
    assert got["spans_ms"] == pytest.approx({"train.batch": 100.0,
                                             "train.gc": 500.0,
                                             "train.fetch": 600.0})
    assert got["window_gc_ms"] == pytest.approx(500.0)
    assert P.slowest_round([], rounds=3) is None


def test_useful_share_reader_reads_the_programs_counters():
    from repro.obs import Counter, reset_counters
    ctx = harness.Context(None, {"rounds": 20}, {})
    reset_counters()
    assert round_useful_share.read(ctx) is None
    Counter("train.client_steps_computed").inc(160)
    Counter("train.client_steps_useful").inc(54)
    assert round_useful_share.read(ctx) == pytest.approx(33.75)
    reset_counters()


def test_scoped_ops_leave_the_reduction_and_its_readers_as_they_were():
    """The harness's reduction of a fixed trace, and the three accepted
    readers on it, read the same numbers whether the operations carry
    scopes or not."""
    plain = [ev("dot", 1.0, 4.0), ev("add", 5.0, 6.0), ev("dot", 7.0, 8.0)]
    scoped = [P.Op(e.name, e.start, e.end, s)
              for e, s in zip(plain, ["attention", "", "aggregate"])]
    spans = [ev("bench.window", 0.0, 10.0), ev("bench.period", 0.0, 9.0),
             ev("bench.round_call", 4.0, 5.0)]
    reds = [T.reduce(T.Trace({"/device:TPU:0": ops}, spans))
            for ops in (plain, scoped)]
    assert reds[0] == reds[1]
    red = reds[0]
    assert (red.window_s, red.busy_s) == (10.0, 5.0)
    assert red.device_ops == [["dot", 4.0], ["add", 1.0]]
    assert dict(red.idle_gaps) == pytest.approx(
        {"bench.period": 4.0, "bench.round_call": 1.0})
    ctx = harness.Context(red, {"rounds": 4, "useful_flops": 1e12},
                          {"bf16_flops_per_s": 1e12})
    assert train_idle_share.read(ctx) == pytest.approx(50.0)
    assert round_device_ms.read(ctx) == pytest.approx(1250.0)
    assert train_mfu.read(ctx) == pytest.approx(10.0)


HLO = """HloModule jit__unknown, is_scheduled=true

%fused_computation.3 (p: f32[4]) -> f32[4] {
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/aggregate/mul"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/while/body/local_step/vmap(transpose(jvp(attention)))/dot_general" stack_frame_id=3}
  %copy-start.2 = f32[4]{0} copy-start(%a)
  ROOT %bitcast_reduce_fusion.7 = f32[4]{0} fusion(%fusion.3), kind=kInput, metadata={op_name="jit(f)/aggregate/reduce_sum"}
}
"""


def test_scopes_from_hlo_by_instruction_inside_the_programs_runs():
    ops = [P.Op("fusion f32[4]", 1.0, 2.0, "", "fusion.3"),
           P.Op("bitcast_reduce_fusion f32[4]", 2.0, 3.0, "",
                "bitcast_reduce_fusion.7"),
           P.Op("copy-start f32[4]", 3.0, 3.5, "", "copy-start.2"),
           # the same instruction name in another program's run
           P.Op("fusion f32[4]", 5.0, 6.0, "", "fusion.3")]
    trace = P.ProgramTrace(
        ops={"/device:TPU:0": ops},
        modules={"/device:TPU:0": [ev("jit__unknown(7)", 0.5, 4.0),
                                   ev("jit_make(9)", 4.5, 6.5)]},
        spans=[], window=(0.0, 7.0))
    got = P.scopes_from_hlo(trace, HLO).ops["/device:TPU:0"]
    assert [e.scope for e in got] == ["attention", "aggregate", "", ""]
    # no run bears the program's name: every operation is looked up
    trace.modules = {}
    got = P.scopes_from_hlo(trace, HLO).ops["/device:TPU:0"]
    assert [e.scope for e in got] == ["attention", "aggregate", "",
                                      "attention"]
