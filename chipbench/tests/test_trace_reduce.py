"""Trace reduction on synthetic events, and on a trace recorded here."""

import pytest

import trace_reduce as tr


def test_busy_idle_and_labelled_gaps():
    devices = {"/device:TPU:0": [("fusion.1", 0, 100), ("fusion.2", 50, 150),
                                 ("xdrop_kernel", 300, 400)]}
    host = [("CountKmer", 0, 200), ("Alignment", 200, 500),
            ("PjitFunction(f)", 160, 290)]
    red = tr.reduce_events(devices, host)
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["idle_share"] == pytest.approx(0.5)
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"Alignment/PjitFunction(f)": 150e-9,
                                  "Alignment": 100e-9})
    ops = dict(red["device_ops"])
    # fusion.2 starts inside fusion.1: it counts in fusion.1's time
    assert ops == pytest.approx({"fusion.1": 100e-9, "xdrop_kernel": 100e-9})


def test_two_devices_average():
    devices = {"/device:TPU:0": [("a", 0, 100)],
               "/device:TPU:1": [("a", 0, 50)]}
    red = tr.reduce_events(devices, [], window=(0, 100))
    assert red["busy_s"] == pytest.approx(75e-9)
    assert dict(red["device_ops"])["a"] == pytest.approx(75e-9)


def test_recorded_trace_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("CountKmer"):
            jnp.ones((64, 64)).sum().block_until_ready()
    devices, host = tr.collect(tr.load(str(tmp_path)))
    assert any(name == "CountKmer" for name, _, _ in host)
    assert all(e >= s for _, s, e in host)
