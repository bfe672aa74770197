"""The readers of the step programs' scopes (`kv_cache.decode`,
`layer_scan.decode`, `mlp_roofline.decode`): on a hand-built trace whose
scopes and self times are known, and on a trace of the program's own
`serve_step` recorded on the CPU backend with the kernels interpreted."""
import glob
import re
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import peaks, run, scopes
from benchmarks.chip import trace as T
from benchmarks.chip import work as W
from benchmarks.chip.tests import smoke

READERS = ("kv_cache.decode", "layer_scan.decode", "mlp_roofline.decode")
BODY = "jit(serve_step)/layers/while/body"
ATTN = BODY + "/closed_call/attn"
PAGED = ATTN + "/paged_attention/jit(paged_attention)"
# (scope, class, self time in ns): 101 ns of serve_step in all, 1 ns of it
# in an instruction with no op_name
DECODE_OPS = [
    ("jit(serve_step)/embed/gather", "other", 2),
    (BODY + "/squeeze", "movement", 10),
    (BODY + "/dynamic_update_slice", "movement", 10),
    (ATTN + "/kv_cache_write/scatter", "kernel", 3),
    (PAGED + "/kv_relayout/reshape", "movement", 17),
    (PAGED + "/pallas_call", "kernel", 8),
    (ATTN + "/qkv/dot_general", "matmul", 5),
    (ATTN + "/out_proj/dot_general", "matmul", 5),
    (BODY + "/closed_call/mlp/dot_general", "matmul", 35),
    ("jit(serve_step)/head/dot_general", "matmul", 4),
    ("jit(serve_step)/add", "other", 1),
    ("", "movement", 1),
]


def reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py")


def hand_trace(ops=DECODE_OPS, module="jit_serve_step(7)"):
    out, t = [], 0
    for i, (scope, cls, ns) in enumerate(ops):
        o = T.Op(0, f"op.{i}", module, t, t + ns, self_ns=ns, scope=scope,
                 cls=cls)
        out.append(o)
        t += ns
    return T.Trace(ops=out, spans=[], window=(0, t), devices=[0])


def context(tr, steps=2, logs=None):
    cfg = smoke.smoke_config("qwen2.5-3b")
    return SimpleNamespace(trace=tr, config=cfg,
                           work={"decode_lengths": [[9, 9]] * steps},
                           peaks=peaks.peaks_for("TPU v5 lite"),
                           log=(logs.append if logs is not None
                                else lambda *a: None))


def test_kv_cache_and_layer_scan_read_their_scopes():
    ctx = context(hand_trace())
    assert reader("kv_cache.decode").read(ctx) == pytest.approx(2000 / 101)
    assert reader("layer_scan.decode").read(ctx) == pytest.approx(2000 / 101)


def test_mlp_roofline_reads_weight_bytes_over_mlp_time():
    """Two steps of the smoke widths' SwiGLU weights read once each, at the
    peak bandwidth, over the 35 ns under `mlp`."""
    ctx = context(hand_trace(), steps=2)
    w = W.widths(ctx.config)
    nbytes = w["layers"] * 3 * w["d"] * w["ff"] * 2
    bound = 2 * nbytes / ctx.peaks["hbm_bytes_s"]
    assert reader("mlp_roofline.decode").read(ctx) == pytest.approx(
        100 * bound / 35e-9)


def test_split_by_scope_puts_each_op_in_its_innermost_part():
    parts = scopes.split(hand_trace(), scopes.DECODE)
    want = {"embed": 2, "layer_scan": 20, "kv_cache_write": 3,
            "kv_relayout": 17, "paged_attention": 8, "qkv": 5,
            "out_proj": 5, "mlp": 35, "head": 4, "none": 1,
            "no_op_name": 1}
    assert {k: v for k, v in parts.items() if v} == pytest.approx(
        {k: v * 1e-9 for k, v in want.items()})

    logs = []
    ctx = context(hand_trace(), logs=logs)
    for name in reversed(READERS):
        reader(name).read(ctx)
    lines = [x for x in logs if x.startswith("scopes serve_step")]
    assert len(lines) == 1, "the split is logged once, by any reader"
    shares = re.search(r"under embed, layers or head (\S+)% of the time "
                       r"\((\S+)% of the time with an op_name\)$", lines[0])
    assert [float(x) for x in shares.groups()] == pytest.approx(
        [9900 / 101, 99.0])


def test_readers_read_nothing_from_a_program_without_layer_scopes():
    """A program that names no layer, as the step programs did before
    their scopes, reads nothing, and says why."""
    ops = [(s.replace("/layers", "").replace("/attn", "")
            .replace("/mlp", ""), c, ns) for s, c, ns in DECODE_OPS]
    for name in READERS:
        logs = []
        assert reader(name).read(context(hand_trace(ops), logs=logs)) is None
        assert any("layers" in x for x in logs), name


def test_scopes_read_nothing_where_the_program_defines_no_scope(monkeypatch):
    """Over a program that has no `repro.scopes`, the module still loads,
    its patterns match nothing, and the readers return None."""
    import repro

    monkeypatch.setitem(sys.modules, "repro.scopes", None)
    monkeypatch.delattr(repro, "scopes")
    monkeypatch.delitem(sys.modules, "bench_scopes", raising=False)
    bare = run.load_module(run.HERE / "scopes.py")
    monkeypatch.delitem(sys.modules, "bench_scopes")
    assert bare.P is None and bare.SPLIT == ()
    logs = []
    assert bare.program_s(context(hand_trace(), logs=logs), "x") is None
    assert any("layers" in x for x in logs)


def test_readers_read_nothing_where_under_99_percent_joined():
    ops = [(s, "" if s.endswith("/squeeze") else c, ns)
           for s, c, ns in DECODE_OPS]
    for name in READERS:
        logs = []
        assert reader(name).read(context(hand_trace(ops), logs=logs)) is None
        assert any("joined" in x for x in logs), name


@pytest.fixture(scope="module")
def decode_trace(tmp_path_factory):
    """Three decode steps of the smoke qwen2.5-3b's `serve_step`, the
    paged kernel interpreted, traced on the CPU backend and joined to the
    program's HLO text."""
    from repro import configs
    from repro.models import lm
    from repro.runtime import steps

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = configs.get_smoke_config("qwen2.5-3b")
    B, T_max, S = 2, 32, 8
    params = lm.init_model(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    cache = lm.init_cache(cfg, B, T_max)
    cache["len"] = jnp.full((B,), S, jnp.int32)
    tok = jnp.ones((B, 1), jnp.int32)
    step = jax.jit(steps.make_serve_step(cfg, configs.ParallelConfig(),
                                         use_kernels=True)).lower(
        params, tok, cache).compile()
    jax.block_until_ready(step(params, tok, cache))
    d = str(tmp_path_factory.mktemp("decode"))
    jax.profiler.start_trace(d)
    lengths = []
    for i in range(3):
        logits, cache = step(params, tok, cache)
        lengths.append([S + i + 1] * B)
    jax.block_until_ready(logits)
    jax.profiler.stop_trace()
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    tr = T.load(path, device_plane=r"^/host:CPU$")
    assert T.attach_hlo(tr, [step.as_text()]) == ["serve_step"]
    return tr, lengths


@pytest.mark.parametrize("name", READERS)
def test_readers_read_the_programs_own_decode_trace(decode_trace, name):
    tr, lengths = decode_trace
    ctx = context(tr)
    ctx.work = {"decode_lengths": lengths}
    value = reader(name).read(ctx)
    assert value is not None and 0 < value <= 100, name
