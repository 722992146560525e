"""The trace reduction on a hand-made trace (every number worked out
below) and on a trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    # chip 0: ops [0,1] [0.5,2] [3,4]; chip 1: [1,2]
    ops = [Event("fusion.1", 0.0, 1.0, 0), Event("fusion.2", 0.5, 1.5, 0),
           Event("kernel", 3.0, 1.0, 0), Event("fusion.1", 1.0, 1.0, 1)]
    modules = [Event("jit_step", 0.0, 2.0, 0), Event("jit_step", 3.0, 1.0, 0),
               Event("jit_step", 1.0, 1.0, 1)]
    host = [Event("bench.batch", -0.5, 5.0), Event("bench.generate", 0.0, 4.2),
            Event("compile", 2.1, 0.8)]
    return tr.Trace(ops, modules, host, 2)


def test_merge_clips_and_joins():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2)], 0.2, 3.5) == \
        [(0.2, 2), (3, 3.5)]
    assert tr.merge([], 0, 1) == []


def test_busy_is_the_union_averaged_over_chips():
    t = synthetic()
    # chip 0: [0,2] + [3,4] = 3 s; chip 1: 1 s; mean 2 s
    assert tr.busy(t, 0.0, 5.0) == pytest.approx(2.0)
    # clipped to [1, 3.5]: chip 0 has 1 + 0.5, chip 1 has 1
    assert tr.busy(t, 1.0, 3.5) == pytest.approx(1.25)


def test_idle_gaps_take_the_innermost_host_span():
    t = synthetic()
    assert tr.idle_gaps(t, 0.0, 5.0) == [(2.0, 1.0), (4.0, 1.0)]
    # gap [2,3]: middle 2.5 lies in "compile" (inside bench.generate);
    # gap [4,5]: middle 4.5 lies only in bench.batch
    assert tr.host_label(t, 2.5) == "compile"
    assert tr.host_label(t, 4.5) == "bench.batch"
    assert tr.host_label(t, -1.0) == ""


def test_breakdown_orders_ops_and_gaps():
    b = tr.breakdown(synthetic(), 0.0, 5.0)
    # fusion.1: (1 + 1) / 2 chips; fusion.2 and kernel: 1.5 / 2 and 1 / 2
    assert b["device_ops"] == [["fusion.1", 1.0], ["fusion.2", 0.75],
                               ["kernel", 0.5]]
    assert b["idle_gaps"] == [["compile", 1.0], ["bench.batch", 1.0]]


def test_executions_and_their_ops():
    t = synthetic()
    runs = tr.executions(t, 0.0, 5.0)
    assert [(r.start, r.dur) for r in runs] == [(0.0, 2.0), (3.0, 1.0)]
    assert [o.name for o in tr.ops_within(t, runs[0])] == ["fusion.1",
                                                          "fusion.2"]
    assert [o.name for o in tr.ops_within(t, runs[1])] == ["kernel"]


def test_json_round_trip(tmp_path):
    t = synthetic()
    tr.save(t, tmp_path / "t.json")
    back = tr.read(tmp_path / "t.json")
    assert back == t
    assert back.window(0.9, 3.5).ops == [t.ops[2], t.ops[3]]


def _batch(names_durs):
    t, mods = 0.0, []
    for name, dur in names_durs:
        mods.append(Event(name, t, dur, 0))
        t += dur + 0.01
    return tr.Trace([], mods, [], 1), t


@pytest.mark.parametrize("two_names", [False, True])
def test_step_runs_finds_prefill_then_decode(two_names):
    from bench import serve_trace
    gen = 3
    pre = "jit_prefill" if two_names else "jit_step"
    seq = [("jit_zeros", 0.001), (pre, 1.0), ("jit_argmax", 0.001)]
    for _ in range(gen):
        seq += [("jit_step", 0.1), ("jit_argmax", 0.001)]
    trace, end = _batch(seq)
    runs = serve_trace.step_runs(trace, 0.0, end, gen)
    assert [r.name for r in runs] == [pre] + ["jit_step"] * gen
    assert runs[0].dur == 1.0
    assert serve_trace.step_runs(trace, 0.0, end, gen + 5) is None


def test_layer_kernels_lie_in_the_loop():
    """Op names as a TPU trace gives them: the instruction's HLO text.
    The attention kernel runs inside the layer loop's ``while``; the
    embedding gather, also a Pallas call, runs before it."""
    from bench import serve_trace
    tpu = 'custom_call_target="tpu_custom_call", backend_config={}'
    gather = f"%_lambda_.1 = bf16[8192,1536] custom-call(%bitcast.171), {tpu}"
    loop = ("%while.4 = (s32[], bf16[32,256,1536]) while(%tuple.61), "
            "condition=%region_7.15, body=%region_0.14")
    attn = f"%closed_call.13 = bf16[32,24,256,128] custom-call(%f.1), {tpu}"
    after = f"%k.2 = bf16[8,8] custom-call(%while.10), {tpu}"
    ops = [Event(gather, 0.0, 0.1, 0), Event(loop, 0.2, 3.0, 0)]
    ops += [Event(attn, 0.3 + i, 0.25, 0) for i in range(3)]
    ops += [Event("%fusion.137 = bf16[40,8192,512] fusion(%while.10)",
                  0.6, 0.5, 0), Event(after, 3.3, 0.1, 0)]
    run = Event("jit__lambda", 0.0, 3.5, 0)
    trace = tr.Trace(sorted(ops, key=lambda e: e.start), [run], [], 1)
    assert [o.start for o in serve_trace.layer_kernels(trace, run)] == \
        [0.3, 1.3, 2.3]
    assert serve_trace.kernel_seconds(trace, [run]) == pytest.approx(0.75)


def recorded():
    """One chat-prefill batch (B 32, S 256, 8 generated) recorded on a TPU
    v5e: the device's operations and program runs, the harness's spans;
    long HLO texts cut to name, opcode and custom-call target."""
    with gzip.open(DATA / "chat_prefill_batch.json.gz", "rt") as f:
        return tr.Trace.from_json(json.load(f))


def test_recorded_batch():
    from bench import flops, serve_trace
    t = recorded()
    batch = next(e for e in t.host if e.name == "bench.batch")
    runs = serve_trace.step_runs(t, batch.start, batch.end, 8)
    assert len(runs) == 9
    assert runs[0].dur == max(r.dur for r in runs)      # the prefill
    # one attention kernel per layer in every run; the embedding gather,
    # the other Pallas call of each run, lies outside the layer loop
    assert [len(serve_trace.layer_kernels(t, r)) for r in runs] == [32] * 9
    pallas = [o for o in tr.ops_within(t, runs[0])
               if "tpu_custom_call" in o.name]
    assert len(pallas) == 33
    secs = serve_trace.kernel_seconds(t, runs[:1])
    assert secs == pytest.approx(0.054797005)
    # least time a layer: 32 x 256 x 64 x (2 x 24 + 2 x 8) x 2 bytes
    # = 67,108,864 B at 819 GB/s = 81.94 us, above 6.47 GFLOP at
    # 197 TFLOP/s = 32.8 us; 32 layers over the kernel's 54.8 ms: 4.785 %
    spec = json.loads((DATA.parents[1] / "configs" /
                       "granite-moe-3b-a800m.json").read_text())
    least, bound = flops.least_time(*flops.flash_attention_cost(spec, 32, 256),
                                    {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9})
    assert bound == "memory"
    assert 100 * 32 * least / secs == pytest.approx(4.785, rel=1e-3)
    busy = tr.busy(t, batch.start, batch.end)
    assert runs[0].dur < busy < batch.dur
    gaps = tr.breakdown(t, batch.start, batch.end)["idle_gaps"]
    assert gaps[0][0] == "bench.generate"
