"""The trace reduction on a small trace recorded on the CPU backend, and
on hand-made intervals."""
import gc
import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import trace as T

CPU_PLANE = r"^/host:CPU$"


def test_union_merges_overlaps_and_keeps_gaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_self_time_of_a_parent_excludes_its_children():
    parent = T.Op(0, "while.1", "m", 0, 10)
    kids = [T.Op(0, "fusion.1", "m", 1, 4), T.Op(0, "fusion.2", "m", 5, 9)]
    T._self_times([parent] + kids)
    assert parent.self_ns == 3
    assert [k.self_ns for k in kids] == [3, 4]


def test_idle_gaps_take_the_open_host_span():
    tr = T.Trace(ops=[T.Op(0, "a", "m", 0, 10), T.Op(0, "b", "m", 30, 40)],
                 spans=[T.Span("prefill", 0, 12), T.Span("sample", 12, 35)],
                 window=(0, 50), devices=[0])
    assert tr.busy_s() == pytest.approx(20e-9)
    gaps = tr.idle_gaps()
    assert [g[0] for g in gaps] == ["sample", "none"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 10e-9])
    assert T.breakdown(tr)["idle_gaps"][0] == ["sample", pytest.approx(20e-9)]


def test_tpu_op_names_are_the_hlo_instruction():
    text = ("%paged_attention.5 = bf16[32,2,8,128]{3,2,1,0} custom-call("
            "s32[32]{0} %x), custom_call_target=\"tpu_custom_call\"")
    assert T.op_name(text) == "paged_attention.5"
    assert T.op_name("dot_general.1") == "dot_general.1"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two calls of a jitted program with a host sleep between them, inside
    named host spans, traced on the CPU backend."""
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("prefill"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("make_batch"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("prefill"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    return T.load(path, span_names=("prefill", "make_batch"),
                  device_plane=CPU_PLANE)


def test_recorded_trace_finds_ops_by_name_and_program(recorded):
    tr = recorded
    assert tr.devices == [0]
    assert tr.op_count(r"^dot") == 2
    assert tr.op_s(r"^dot") > 0
    assert tr.module_s(r"lambda") > 0
    assert all("lambda" in o.module for o in tr.select(r"^dot"))


def test_recorded_trace_attributes_the_sleep_to_its_span(recorded):
    tr = recorded
    assert 0.05 <= tr.window_s() < 5
    assert 0 < tr.busy_s() < tr.window_s() - 0.05
    longest = max(tr.idle_gaps(), key=lambda g: g[1])
    assert longest[0] == "make_batch" and longest[1] >= 0.045
    bd = T.breakdown(tr)
    assert bd["idle_gaps"][0][0] == "make_batch"
    assert len(bd["device_ops"]) <= 10 and bd["device_ops"][0][1] > 0


# ------------------------------------------------------------- HLO join
def _scoped_step(x, cache, row, i):
    with jax.named_scope("mlp"):
        h = jnp.tanh(x @ x)
    with jax.named_scope("kv_cache"):
        cache = jax.lax.dynamic_update_slice(cache, row, (i, jnp.uint32(0)))
    with jax.named_scope("attn"):
        s, _ = jax.lax.scan(lambda c, r: (c + jnp.exp(r).sum(), None),
                            jnp.float32(0), cache)
    return h.sum() + s, cache


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """A program with three named scopes (a matmul, a cache update, a
    scan), traced on the CPU backend and joined to its own HLO text."""
    d = str(tmp_path_factory.mktemp("scoped"))
    args = (jnp.ones((256, 256)), jnp.zeros((64, 256)), jnp.ones((1, 256)),
            jnp.uint32(3))
    compiled = jax.jit(_scoped_step).lower(*args).compile()
    compiled(*args)[0].block_until_ready()
    jax.profiler.start_trace(d)
    compiled(*args)[0].block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    tr = T.load(path, device_plane=CPU_PLANE)
    return tr, T.attach_hlo(tr, [compiled.as_text()])


def test_join_gives_every_op_its_instruction(scoped):
    tr, modules = scoped
    assert modules == ["_scoped_step"]
    joined, unnamed = T.join_shares(tr, "_scoped_step")
    assert joined == 1.0 and 0 <= unnamed < 1
    assert all(o.cls in T.CLASSES for o in tr.ops)


def test_join_reads_scope_and_class(scoped):
    tr, _ = scoped

    def classes(scope):
        return {o.cls for o in tr.ops if o.scope.endswith(scope)}

    assert classes("/mlp/dot_general") == {"matmul"}
    assert classes("/kv_cache/dynamic_update_slice") == {"movement"}
    assert classes("/attn/while") == {"control"}
    assert classes("/attn/while/body/closed_call/exp") == {"other"}


def test_scope_and_class_sums_are_self_times(scoped):
    tr, _ = scoped
    ops = tr.select(module="_scoped_step")
    total = sum(o.self_ns for o in ops) * 1e-9
    assert sum(tr.cls_s(c, "_scoped_step") for c in T.CLASSES) == \
        pytest.approx(total)
    for scope in ("/mlp/", "/kv_cache/", "/attn/"):
        want = sum(o.self_ns for o in ops if scope in o.scope) * 1e-9
        assert 0 < tr.scope_s(scope, "_scoped_step") == pytest.approx(want)


def test_join_leaves_ops_of_other_programs_alone(recorded):
    """A program whose text was not given keeps no scope and no class, and
    every existing query reads as before."""
    before = [(o.name, o.module, o.start, o.end, o.self_ns)
              for o in recorded.ops]
    assert T.attach_hlo(recorded, []) == []
    assert all(o.cls == "" and o.scope == "" for o in recorded.ops)
    assert [(o.name, o.module, o.start, o.end, o.self_ns)
            for o in recorded.ops] == before
    assert T.join_shares(recorded, "lambda") == (0.0, 0.0)


def test_classes_of_opcodes():
    assert T.classify({"parameter", "dynamic-slice", "bitcast"}) == \
        "movement"
    assert T.classify({"parameter", "convolution", "add"}) == "matmul"
    assert T.classify({"custom-call"}) == "kernel"
    assert T.classify({"while"}) == "control"
    assert T.classify({"parameter", "copy", "multiply"}) == "other"


def test_parser_on_the_chips_decode_program():
    """An excerpt of `serve_step` as compiled for a TPU v5e (qwen2.5-3b,
    batch 32, cache 1024): the trace's module `jit_serve_step(<id>)` joins
    the text's `jit_serve_step`, and each operation gets its class."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "serve_step_excerpt.hlo.txt")
    with open(path) as f:
        text = f.read()
    want = {
        "reshape.207": ("movement", "jit(paged_attention)/reshape"),
        "dynamic-slice_bitcast_fusion.4": ("movement", "/while/body/squeeze"),
        "bitcast_dynamic-update-slice_fusion.4":
            ("movement", "/while/body/dynamic_update_slice"),
        "fusion.125": ("matmul", "closed_call/dot_general"),
        "paged_attention.5": ("kernel", "jit(paged_attention)/pallas_call"),
        "while.2": ("control", "jit(serve_step)/while"),
    }
    ops = [T.Op(0, name, "jit_serve_step(4242)", i, i + 1)
           for i, name in enumerate(want)]
    ops.append(T.Op(0, "fusion.1", "jit_prefill_step(7)", 10, 11))
    tr = T.Trace(ops=ops, spans=[], window=(0, 20), devices=[0])
    assert T.attach_hlo(tr, [text]) == ["serve_step"]
    for o in ops[:-1]:
        cls, scope = want[o.name]
        assert o.cls == cls and o.scope.endswith(scope), o
    assert ops[-1].cls == "" and ops[-1].scope == ""


def test_gc_spans_cover_each_collection(tmp_path):
    """The harness's `gc.callbacks` entry writes a host span named "gc"
    over a collection, and an instant inside it reads "gc"."""
    from benchmarks.chip import run

    on_gc = run.gc_spans()
    jax.profiler.start_trace(str(tmp_path))
    gc.callbacks.append(on_gc)
    try:
        with jax.profiler.TraceAnnotation("window"):
            gc.collect()
    finally:
        gc.callbacks.remove(on_gc)
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)[0]
    tr = T.load(path, span_names=("gc",), device_plane=CPU_PLANE)
    spans = [s for s in tr.spans if s.name == "gc"]
    assert spans and all(s.end >= s.start for s in spans)
    s = spans[-1]
    assert tr.span_at((s.start + s.end) / 2) == "gc"
