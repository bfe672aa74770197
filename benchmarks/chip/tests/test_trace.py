"""The trace reduction on a small trace recorded on the CPU backend, and
on hand-made intervals."""
import glob
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
