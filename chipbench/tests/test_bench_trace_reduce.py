"""The reduction from a trace to busy time, time by layer and the
breakdown, on synthetic events and on a small recorded trace."""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace_reduce as TR

TABLE = {"device_plane_prefix": "/device:TPU:", "op_lines": "^XLA Ops$",
         "meta_stats": [],
         "rules": [{"layer": "aggregation", "pattern": "scatter|gather"},
                   {"layer": "dense", "pattern": "dot"}]}


def _compile(t):
    import re
    t["compiled"] = [(r["layer"], re.compile(r["pattern"]))
                     for r in t["rules"]]
    return t


def _op(name, s, e):
    return TR.Op(name, s, e, name)


def test_busy_is_the_union_of_operations_inside_the_window():
    ops = {"/device:TPU:0": [_op("scatter.1", 100, 300), _op("dot.2", 200, 400),
                             _op("fusion.3", 600, 700),
                             _op("gather.4", 950, 1200)]}
    host = [("window", 0, 1000), ("loader_wait", 400, 600),
            ("fetch", 700, 1000), ("sample", 0, 1000)]
    r = TR.reduce(ops, host, _compile(dict(TABLE)))
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100, 400) + [600, 700) + [950, 1000) clipped to the window
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["layer_s"]["aggregation"] == pytest.approx((200 + 50) * 1e-9)
    assert r["layer_s"]["dense"] == pytest.approx(200e-9)
    assert r["layer_s"]["other"] == pytest.approx(100e-9)
    gaps = dict((n, round(s * 1e9)) for n, s in r["breakdown"]["idle_gaps"])
    # [0, 100) untraced, [400, 600) waiting on the loader, [700, 950) fetching
    assert gaps == {"untraced host": 100, "loader_wait": 200, "fetch": 250}
    assert r["idle_by_label"]["fetch"] == pytest.approx(250e-9)
    top = r["breakdown"]["device_ops"][0]
    assert top[0] in ("aggregation: scatter.1", "dense: dot.2")
    assert r["breakdown"]["device_ops"][0][1] == pytest.approx(200e-9)


def test_chips_are_averaged():
    ops = {"/device:TPU:0": [_op("dot", 0, 100)],
           "/device:TPU:1": [_op("dot", 0, 300)]}
    r = TR.reduce(ops, [("window", 0, 400)], _compile(dict(TABLE)))
    assert r["busy_s"] == pytest.approx(200e-9) and r["planes"] == 2
    assert r["layer_s"]["dense"] == pytest.approx(200e-9)


def test_no_device_plane_reads_no_busy_time():
    r = TR.reduce({}, [("window", 0, 10)], _compile(dict(TABLE)))
    assert r["planes"] == 0 and r["busy_s"] == 0.0


def test_op_layers_table_and_peaks_load():
    t = TR.load_table(edges=[266240, 25600])
    assert t["compiled"] and all(layer for layer, _ in t["compiled"])
    # op names on the chip are their HLO text (as recorded on a v5e)
    pallas = ("%jvp_jit__gss_unfused_jit__.2 = f32[26752,1024] custom-call("
              "s32[266240,1] %copy.6), custom_call_target=\"tpu_custom_call\"")
    scatter = ("%fusion.9 = f32[169343,256] fusion(s32[2484941] %gte, "
               "f32[2484941,256] %select_multiply_fusion.1)")
    dense = "%fusion.90 = f32[169343,256] fusion(f32[169343,128] %copy)"
    assert TR.layer_of(TR.Op(pallas, 0, 1, pallas), t) == "aggregation"
    g = TR.load_table(edges=[2484941])
    assert TR.layer_of(TR.Op(scatter, 0, 1, scatter), g) == "aggregation"
    assert TR.layer_of(TR.Op(dense, 0, 1, dense), g) == "other"
    assert TR.layer_of(TR.Op(scatter, 0, 1, scatter),
                       TR.load_table()) == "other"
    peak = TR.load_peak("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        TR.load_peak("cpu")


def test_a_recorded_trace_is_read(tmp_path):
    """On the CPU the operations sit on a host plane; pointing the table at
    it exercises the reader on a real ``.xplane.pb``, recorded with the
    options of a traced run: the harness's spans are there."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=TR.profile_options())
    with jax.profiler.TraceAnnotation("harness.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("harness.dispatch"):
                y = f(x)
            y.block_until_ready()
    jax.profiler.stop_trace()
    table = _compile(dict(TABLE, device_plane_prefix="/host:CPU",
                          op_lines="XLA", meta_stats=["hlo_op",
                                                      "hlo_module"]))
    device, host = TR.load_events(str(tmp_path), table)
    names = [n for n, _, _ in host]
    assert names.count("dispatch") == 3 and "window" in names
    ops = [o for plane in device.values() for o in plane]
    assert ops and any("jit_" in o.meta for o in ops)
    r = TR.reduce(device, host, table)
    assert 0 < r["window_s"] and r["busy_s"] <= r["window_s"] * len(device)
    json.dumps(r)
