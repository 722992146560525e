"""Layer times joined to the programs' own HLO: the wire reader on a CPU
profile, the compiled step programs against the HLO a profile of
``generate`` holds, the join on hand-made traces, and the readers on
batches recorded on a TPU v5e."""
import dataclasses
import gzip
import json
from pathlib import Path

import pytest

from bench import hlo_scopes, layer_time, run as run_mod, serve_trace
from bench import trace_reduce as tr
from bench.trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"
SPEC = json.loads((DATA.parents[1] / "configs" /
                   "granite-moe-3b-a800m.json").read_text())
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
OLD_READERS = ("generate_compile_ms", "idle_share.serve", "mfu.prefill",
               "mfu.decode", "flash_attention_roofline",
               "decode_attention_roofline")
NEW_READERS = ("experts_ms.prefill", "experts_ms.decode",
               "attention_ms.decode", "attention_layout_ms.decode")


def test_wire_reader_on_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def two_scopes(x, w):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("experts"):
            return jnp.sin(y @ w.T)

    x = jnp.ones((16, 16))
    two_scopes(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    two_scopes(x, x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    programs = hlo_scopes.read(path.read_bytes())
    name = next(k for k in programs if k.startswith("jit_two_scopes("))
    paths = set(programs[name].values())
    assert "jit(two_scopes)/attention/dot_general" in paths
    assert "jit(two_scopes)/experts/sin" in paths


def test_step_hlo_is_the_hlo_that_ran(tmp_path):
    """The op paths of the step programs compiled again are those of the
    programs a traced ``generate`` call ran (CPU, reduced config)."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch import serve
    from repro.model import lm

    cfg = configs.get_reduced("granite-moe-3b-a800m")
    B, S, gen = 2, 8, 3
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jnp.ones((B, S), jnp.int32)
    jax.profiler.start_trace(str(tmp_path))
    serve.generate(params, cfg, prompts, gen=gen)
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    ran = hlo_scopes.read(path.read_bytes())
    want = layer_time.step_hlo(cfg, B, S, gen)
    for program, names in want.items():
        traced = [v for k, v in ran.items()
                  if k.startswith(layer_time.PROGRAMS[program])]
        assert traced == [names]
        scopes = {layer_time.scope_of(p) for p in names.values()}
        assert {"embed", "attention", "experts", "head"} <= scopes


def test_wire_reader_without_hlo():
    # an XSpace with one plane named "/device:TPU:0" (field 1 of XSpace,
    # field 2 of XPlane) and no metadata plane
    plane = b"\x12\x0d/device:TPU:0"
    assert hlo_scopes.read(b"\x0a" + bytes([len(plane)]) + plane) == {}
    assert hlo_scopes.read(b"") == {}


def test_scope_is_the_outermost_layer_on_the_path():
    of = layer_time.scope_of
    assert of("jit(decode)/while/body/closed_call/experts/router/top_k") \
        == "experts"
    assert of("jit(decode)/while/body/closed_call/attention/decode_attention"
              "/pallas_call") == "attention"
    assert of("jit(decode)/embed/burst_gather/pallas_call") == "embed"
    assert of("jit(decode)/while/body/dynamic_update_slice") == "other"
    assert of("") == "other"


def _op(name, start, dur):
    return Event(f"%{name} = fusion(...)", start, dur, 0)


def synthetic(unmatched_s=0.0):
    """A prefill run and two decode runs of the step; in each decode run a
    layer loop (``while``) holds an attention kernel, a layout copy and an
    expert fusion; ``gather`` and ``head`` lie outside it."""
    ops, modules = [], [Event("jit_prefill(7)", 0.0, 1.0, 0)]
    ops += [_op("fusion.1", 0.0, 0.6), _op("fusion.2", 0.6, 0.3)]
    for t in (2.0, 3.0):
        modules.append(Event("jit_decode(9)", t, 0.9, 0))
        modules.append(Event("jit__argmax(3)", t + 0.95, 0.01, 0))
        ops += [_op("gather", t, 0.05),
                Event("%while.3 = while(...)", t + 0.1, 0.7, 0),
                _op("kernel", t + 0.1, 0.4), _op("copy.5", t + 0.5, 0.1),
                _op("experts", t + 0.6, 0.2), _op("head", t + 0.8, 0.05)]
        if unmatched_s:
            ops.append(_op("unknown", t + 0.85, unmatched_s))
    ops.sort(key=lambda e: e.start)
    host = [Event("bench.batch", -0.1, 4.0)]
    return tr.Trace(ops, modules, host, 1)


_STEP = "jit(decode)/while/body/closed_call"
#: the op paths of the synthetic trace's programs
SYNTHETIC_HLO = {
    "prefill": {"fusion.1": "jit(prefill)/while/body/experts/dot",
                "fusion.2": "jit(prefill)/head/dot_general"},
    "decode": {
        "gather": "jit(decode)/embed/burst_gather/pallas_call",
        "while.3": "jit(decode)/while",
        "kernel": f"{_STEP}/attention/decode_attention/pallas_call",
        "copy.5": f"{_STEP}/attention/transpose",
        "experts": f"{_STEP}/experts/etd,edf->etf/dot_general",
        "head": "jit(decode)/head/dot_general"}}


def test_leaf_ops_leave_out_the_loop():
    t = synthetic()
    run = t.modules[1]
    assert [o.name.split()[0] for o in layer_time.leaf_ops(t, run)] == [
        "%gather", "%kernel", "%copy.5", "%experts", "%head"]


def test_split_by_scope():
    t = synthetic()
    runs = layer_time.step_runs(t, -0.1, 3.9, 2)
    assert [r.start for r in runs["decode"]] == [2.0, 3.0]
    s = layer_time.split(t, runs["decode"], SYNTHETIC_HLO["decode"])
    assert s.runs == 2
    assert s.total == pytest.approx({"embed": 0.1, "attention": 1.0,
                                     "experts": 0.4, "head": 0.1})
    assert s.kernel == pytest.approx({"embed": 0.1, "attention": 0.8})
    leaf = sum(o.dur for r in runs["decode"]
               for o in layer_time.leaf_ops(t, r))
    assert sum(s.total.values()) == pytest.approx(leaf)
    p = layer_time.split(t, runs["prefill"], SYNTHETIC_HLO["prefill"])
    assert p.total == pytest.approx({"experts": 0.6, "head": 0.3})
    # a batch whose step programs carry other names: no runs
    assert layer_time.step_runs(t, -0.1, 3.9, 3) is None


@pytest.mark.parametrize("unmatched_s, joined", [(0.009, True),
                                                 (0.011, False)])
def test_a_failed_join_reads_nothing(unmatched_s, joined):
    # leaf time of a decode run: 0.8 + the unmatched op's
    t = synthetic(unmatched_s * 0.8 / (1 - unmatched_s))
    runs = layer_time.step_runs(t, -0.1, 3.9, 2)["decode"]
    s = layer_time.split(t, runs, SYNTHETIC_HLO["decode"])
    assert (s is not None) == joined
    if joined:
        assert s.unmatched == pytest.approx(2 * unmatched_s * 0.8 /
                                            (1 - unmatched_s))


def ctx_for(trace, B, S, gen):
    batch = next(e for e in trace.host if e.name == "bench.batch")
    b = run_mod.Batch(B=B, S=S, gen=gen, t_call=0.0, t_ret=1.0,
                      compile_s=0.5, traced=True)
    return run_mod.Context(
        cell=None, spec=SPEC, batches=[b], setup_s=1.0, window=(0.0, 1.0),
        device={}, peak=PEAK, trace=trace, traced=(batch.start, batch.end),
        traced_spans=[(batch.start, batch.end)])


def test_add_splits():
    t = synthetic()
    runs = layer_time.step_runs(t, -0.1, 3.9, 2)["decode"]
    one = [layer_time.split(t, [r], SYNTHETIC_HLO["decode"]) for r in runs]
    both = layer_time.add(*one)
    whole = layer_time.split(t, runs, SYNTHETIC_HLO["decode"])
    assert both.runs == whole.runs == 2
    assert both.total == pytest.approx(whole.total)
    assert both.kernel == pytest.approx(whole.kernel)


def step_hlo_of(monkeypatch, hlo):
    """The readers join to ``hlo`` in place of the compiled programs."""
    seen = []

    def step_hlo(cfg, B, S, gen):
        seen.append((cfg.name, B, S, gen))
        return hlo
    monkeypatch.setattr(layer_time, "step_hlo", step_hlo)
    return seen


def test_synthetic_readers(monkeypatch):
    seen = step_hlo_of(monkeypatch, SYNTHETIC_HLO)
    ctx = ctx_for(synthetic(), 1, 1, 2)
    ms = {n: run_mod.reader(n)(ctx) for n in NEW_READERS}
    assert set(seen) == {("granite-moe-3b-a800m", 1, 1, 2)}
    assert ms == pytest.approx({"experts_ms.prefill": 600.0,
                                "experts_ms.decode": 200.0,
                                "attention_ms.decode": 500.0,
                                "attention_layout_ms.decode": 100.0})
    # no trace, or a trace with no HLO: nothing
    assert run_mod.reader("attention_ms.decode")(
        dataclasses.replace(ctx, trace=None)) is None
    # programs whose instructions are not the trace's: a failed join
    step_hlo_of(monkeypatch, {"prefill": {}, "decode": {}})
    assert run_mod.reader("attention_ms.decode")(ctx) is None


def _recorded(name):
    """The recorded batch's trace and the op paths of its programs
    ({program name: {instruction name: op_name}})."""
    with gzip.open(DATA / name, "rt") as f:
        d = json.load(f)
    return tr.Trace.from_json(d), d.get("programs", {})


def test_old_recording_reads_as_before(monkeypatch):
    """The chat-prefill batch recorded before the step programs had names
    (one name for prefill and decode): the readers that predate the layer
    readers read what they read at the parent, and the layer readers read
    nothing, without compiling a program."""
    seen = step_hlo_of(monkeypatch, {})
    t, programs = _recorded("chat_prefill_batch.json.gz")
    assert programs == {}
    want = {"generate_compile_ms": 500.0,
            "idle_share.serve": 82.50944258175898,
            "mfu.prefill": 16.176582562701846,
            "mfu.decode": 0.6083024152216935,
            "flash_attention_roofline": 4.785079323747997,
            "decode_attention_roofline": 2.1987672469195685}
    ctx = ctx_for(t, 32, 256, 8)
    assert {n: run_mod.reader(n)(ctx) for n in OLD_READERS} == \
        pytest.approx(want, rel=1e-12)
    assert all(run_mod.reader(n)(ctx) is None for n in NEW_READERS)
    assert seen == []
    lo, hi = ctx.traced
    assert tr.breakdown(t, lo, hi)["device_ops"][0] == [
        "%while.4 = while(...)", 0.41799813]


#: (file, B, S, gen, the new readers' values) of batches recorded on a TPU
#: v5e with ``bench/tests/record.py`` (seeds 3141592653 and 2718281828)
RECORDED = [
    ("chat_prefill_scoped_batch.json.gz", 32, 256, 8,
     {"experts_ms.prefill": 310.1156769999997,
      "experts_ms.decode": 8.197745625000037,
      "attention_ms.decode": 32.08154400000029,
      "attention_layout_ms.decode": 1.3950292500001273}),
    ("decode_heavy_batch.json.gz", 64, 128, 128,
     {"experts_ms.prefill": 310.1147899999995,
      "experts_ms.decode": 8.169341882813619,
      "attention_ms.decode": 47.95569793749778,
      "attention_layout_ms.decode": 3.667140937497662}),
]


@pytest.mark.parametrize("name, B, S, gen, want", RECORDED,
                         ids=[r[0].split("_batch")[0] for r in RECORDED])
def test_recorded_batch_by_layer(monkeypatch, name, B, S, gen, want):
    t, programs = _recorded(name)
    hlo = {k: next(v for p, v in programs.items() if p.startswith(prefix))
           for k, prefix in layer_time.PROGRAMS.items()}
    step_hlo_of(monkeypatch, hlo)
    ctx = ctx_for(t, B, S, gen)
    assert {n: run_mod.reader(n)(ctx) for n in NEW_READERS} == \
        pytest.approx(want, rel=1e-9)
    runs = layer_time.step_runs(t, *ctx.traced, gen)
    for program, rs in runs.items():
        s = layer_time.split(t, rs, hlo[program])
        # every leaf operation joined, and the layers add up to the runs'
        # leaf time
        assert s.unmatched == 0.0
        leaf = sum(o.dur for r in rs for o in layer_time.leaf_ops(t, r))
        assert sum(s.total.values()) == pytest.approx(leaf, rel=1e-9)
        assert set(s.total) <= set(layer_time.SCOPES) | {"other"}
        # the kernels: the embedding gather and the attention kernel only
        assert set(s.kernel) == {"embed", "attention"}
    # the attention kernel's part, found by its op path, is the time of the
    # Pallas calls that ``serve_trace`` finds inside the layer loop
    s = layer_time.split(t, runs["decode"], hlo["decode"])
    assert s.kernel["attention"] == pytest.approx(
        serve_trace.kernel_seconds(t, runs["decode"]), rel=0.01)
    assert 1e3 * s.kernel["attention"] / gen == pytest.approx(
        want["attention_ms.decode"] - want["attention_layout_ms.decode"])
    # the readers that predate the scopes still find the step's runs
    assert run_mod.reader("decode_attention_roofline")(ctx) > 0
