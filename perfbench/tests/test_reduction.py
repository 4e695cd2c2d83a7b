"""The trace reduction: interval arithmetic, op classes and the readers, on
hand-made intervals and on a slice of a trace recorded on a TPU v5e."""
import json
import os

import pytest

from perfbench import flops, harness, tracing
from perfbench.tracing import Trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MODEL = json.load(open(os.path.join(ROOT, "perfbench", "configs", "gpt-6.7b.json")))
PEAK = json.load(open(os.path.join(ROOT, "perfbench", "peaks.json")))["TPU v5 lite"]
SLICE = os.path.join(os.path.dirname(__file__), "data", "tpu_v5e_fpdt64k_slice.textproto")
HOST = "T(8,128)(2,1)S(5)"


def test_union_and_uncovered():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert tracing.uncovered([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert tracing.uncovered([(0, 4), (6, 10)], [(3, 7)]) == 6
    assert tracing.uncovered([(0, 10)], []) == 10
    assert tracing.clip([(0, 4), (6, 10), (11, 12)], 2, 8) == [(2, 4), (6, 8)]


def test_opcodes_and_kinds():
    f = tracing.op("%fusion.12 = bf16[4,8]{1,0:T(8,128)} fusion(bf16[4,8] %p), kind=kLoop", 0, 1)
    k = tracing.op('%closed_call.9 = (f32[1,32,4096,128]{3,2,1,0}) custom-call(s32[2]{0} %x), '
                   'custom_call_target="tpu_custom_call"', 0, 1)
    w = tracing.op("%host-call.1.cloned.1.call-done = bf16[16]{0:" + HOST + "} async-done(x)", 0, 1)
    loop = tracing.op("%while.3 = (s32[]{:T(128)}) while(s32[] %a), body=%b", 0, 1)
    assert [o.opcode for o in (f, k, w, loop)] == ["fusion", "custom-call", "async-done", "while"]
    assert [tracing.is_compute(o) for o in (f, k, w, loop)] == [True, True, False, False]
    assert tracing.kind(k) == "custom-call:tpu_custom_call"
    assert tracing.kind(w) == "async-done:host-call.cloned.call-done"
    assert tracing.kind(f) == "fusion" and tracing.is_host_transfer(w)


def _op(text, s, e):
    return tracing.op(text, s, e)


def _reading(devices, asyncs=None, window=(0, 1000), steps=1, spans=()):
    trace = Trace(devices, asyncs or {p: [] for p in devices},
                  [("bench.window", *window), *spans], window)
    spec = {"model": MODEL, "traffic": {"batch": 1, "seq": 65536}}
    return harness.Reading(spec, PEAK, len(devices), steps, (window[1] - window[0]) / 1e9, trace)


FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8] %a)"
KERNEL = '%pallas_call.{} = f32[8]{{0}} custom-call(s32[2] %o), custom_call_target="tpu_custom_call"'
FETCH = "%dynamic-slice-start.{} = ((bf16[16,8]{{1,0:" + HOST + "}}), bf16[1,8]{{1,0}}) async-start(x)"
WAIT = "%dynamic-slice-done.{} = bf16[1,8]{{1,0}} async-done(x)"


def test_idle_and_exposed_host_transfer_shares():
    dev0 = [_op(FUSION.format(1), 0, 300), _op(WAIT.format(1), 300, 520),
            _op(KERNEL.format(1), 600, 800)]
    dev1 = [_op(FUSION.format(1), 0, 900)]
    asyncs = {"/device:TPU:0": [_op(FETCH.format(1), 250, 520)], "/device:TPU:1": []}
    r = _reading({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, asyncs)
    # device 0 computes over (0, 300) and (600, 800): the wait is idle
    assert harness.reader("device_idle_pct")(r) == pytest.approx(100 * 500 / 1000)
    # the fetch is in flight over (250, 520); compute covers (250, 300)
    assert harness.reader("host_copy_exposed_pct")(r) == pytest.approx(100 * 220 / 1000)
    assert harness.reader("pallas_kernel_s_per_step")(r) == pytest.approx(200 / 2 / 1e9)
    assert tracing.busy_s(r.trace) == pytest.approx((500 + 900) / 2 / 1e9)


A2A = "%all-to-all.{} = bf16[8]{{0}} all-to-all(bf16[8] %a), replica_groups={{{{0,1,2,3}}}}"
AG_START = "%all-gather-start.{} = (bf16[2]{{0}}, bf16[8]{{0}}) all-gather-start(bf16[2] %a)"
AG_DONE = "%all-gather-done.{} = bf16[8]{{0}} all-gather-done(x)"


def test_collective_kinds():
    ops = [_op(A2A.format(1), 0, 1), _op(AG_START.format(1), 0, 1), _op(AG_DONE.format(1), 0, 1),
           _op("%all-reduce-scatter-fusion.2 = f32[8]{0} fusion(f32[8] %a), kind=kOutput", 0, 1),
           _op("%async-collective-done.3 = bf16[64,8]{1,0} fusion(bf16[16,8] %a)", 0, 1),
           _op(FUSION.format(1), 0, 1), _op(KERNEL.format(1), 0, 1), _op(FETCH.format(1), 0, 1)]
    assert [tracing.is_collective(o) for o in ops] == [True] * 5 + [False] * 3


def test_collective_hidden_under_compute_reads_zero():
    dev = [_op(FUSION.format(1), 0, 600), _op(AG_DONE.format(1), 600, 610),
           _op(FUSION.format(2), 610, 1000)]
    asyncs = {"/device:TPU:0": [_op(AG_START.format(1), 100, 600)]}
    r = _reading({"/device:TPU:0": dev}, asyncs)
    assert harness.reader("collective_exposed_pct")(r) == 0.0


def test_exposed_all_to_all_reads_its_share():
    # device 0: a synchronous all-to-all over (300, 450) with nothing else
    # running; device 1: an all-gather in flight over (0, 300), compute over
    # (0, 100): 200 exposed.  The worst device's share is read.
    dev0 = [_op(FUSION.format(1), 0, 300), _op(A2A.format(1), 300, 450),
            _op(FUSION.format(2), 450, 1000)]
    dev1 = [_op(FUSION.format(1), 0, 100), _op(AG_DONE.format(1), 100, 300),
            _op(FUSION.format(2), 300, 1000)]
    asyncs = {"/device:TPU:0": [], "/device:TPU:1": [_op(AG_START.format(1), 0, 300)]}
    r = _reading({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, asyncs)
    assert harness.reader("collective_exposed_pct")(r) == pytest.approx(100 * 200 / 1000)
    dev1[1:2] = []
    asyncs["/device:TPU:1"] = []
    r = _reading({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, asyncs)
    assert harness.reader("collective_exposed_pct")(r) == pytest.approx(100 * 150 / 1000)


def test_nothing_to_read():
    r = _reading({"/device:TPU:0": [_op(FUSION.format(1), 0, 900)]})
    assert harness.reader("host_copy_exposed_pct")(r) is None
    assert harness.reader("pallas_kernel_s_per_step")(r) is None
    assert harness.reader("collective_exposed_pct")(r) is None


def test_attention_events_are_matched_by_kernel_name():
    ops = [_op(KERNEL.format(7) + " _fwd_kernel", 0, 400),
           _op("%flash_attention_dq.2 = f32[8]{0} custom-call(x)", 400, 500),
           _op(FUSION.format(3) + " _fwd_kernel_like_name_elsewhere", 500, 900)]
    r = _reading({"/device:TPU:0": ops}, window=(0, 10 ** 9))
    least = flops.attention_work(MODEL, 1, 65536)["flops"] / PEAK["bf16_flops_per_s"]
    assert harness.reader("flash_attention_roofline")(r) == pytest.approx(100 * least / 500e-9)


def test_no_attention_event_is_an_error():
    r = _reading({"/device:TPU:0": [_op(KERNEL.format(1), 0, 900)]})
    with pytest.raises(RuntimeError, match="no attention kernel"):
        harness.reader("flash_attention_roofline")(r)


def test_step_mfu():
    r = _reading({"/device:TPU:0": [_op(FUSION.format(1), 0, 900)]}, window=(0, 2 * 10 ** 9),
                 steps=3)
    want = 100 * 3 * flops.step_flops(MODEL, 1, 65536) / (2.0 * PEAK["bf16_flops_per_s"])
    assert harness.reader("step_mfu")(r) == pytest.approx(want)


def test_breakdown_names_gaps_by_host_span_and_device_wait():
    ops = [_op(FUSION.format(1), 0, 100), _op(WAIT.format(2), 100, 400),
           _op(FUSION.format(2), 400, 1000)]
    r = _reading({"/device:TPU:0": ops}, spans=[("bench.feed", 0, 50), ("bench.block", 50, 1000)])
    b = harness.breakdown(r.trace)
    assert b["device_ops"][0] == ["fusion", 700 / 1e9]
    assert b["idle_gaps"] == [["bench.block | async-done:dynamic-slice-done", 300 / 1e9]]


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with open(SLICE) as f:
        return tracing.from_profile(ProfileData.from_text_proto(f.read()))


def _sampled_share(trace, plane, inside, step=250):
    """Share of the window's sample points (every ``step`` ns) that ``inside`` takes."""
    lo, hi = trace.window
    pts = range(int(lo), int(hi), step)
    return sum(inside(t) for t in pts) / len(pts)


def test_recorded_slice_against_sampling(recorded):
    plane = "/device:TPU:0"
    ops, asyncs = recorded.devices[plane], recorded.asyncs[plane]
    assert {"fusion", "custom-call", "async-done", "while"} <= {o.opcode for o in ops}
    assert any(tracing.is_host_transfer(o) for o in asyncs)
    compute = [o for o in ops if tracing.is_compute(o)]
    flight = [o for o in asyncs if tracing.is_host_transfer(o)]

    def busy(t):
        return any(o.start <= t < o.end for o in compute)

    def exposed(t):
        return any(o.start <= t < o.end for o in flight) and not busy(t)

    r = _reading(recorded.devices, recorded.asyncs, window=recorded.window)
    r.trace = recorded
    idle = harness.reader("device_idle_pct")(r)
    assert idle == pytest.approx(100 * (1 - _sampled_share(recorded, plane, busy)), abs=0.1)
    assert 0 < idle < 100
    assert harness.reader("host_copy_exposed_pct")(r) == pytest.approx(
        100 * _sampled_share(recorded, plane, exposed), abs=0.1)
