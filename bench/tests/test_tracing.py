"""The trace reductions on a small synthetic trace."""
import pytest

from bench import tracing, work
from bench.harness import load_module
from bench.tracing import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, kind, start_us, dur_us, plane=DEV):
    return Event(plane, "XLA Ops", f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p)",
                 start_us * 1e3, dur_us * 1e3)


def trace():
    """A 100 us window: a while loop holding three ops (10 + 20 + 10 us busy),
    a 30 us copy, and idle stretches under two host spans."""
    return [
        Event(HOST, "python", tracing.WINDOW_SPAN, 0.0, 100e3),
        Event(HOST, "python", "bench.serve.tick", 0.0, 30e3),
        Event(HOST, "python", "bench.serve.idle", 60e3, 40e3),
        Event(DEV, "XLA Ops", "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
              10e3, 40e3),
        op("fusion.1", "fusion", 10, 10),
        op("fusion.2", "fusion", 25, 20),
        op("fusion.1", "fusion", 45, 5),
        op("copy-start.1", "copy-start", 55, 30),
    ]


def test_op_kind_and_name():
    e = op("all-gather.7", "all-gather", 0, 1)
    assert tracing.op_kind(e.name) == "all-gather"
    assert tracing.op_name(e.name) == "all-gather.7"
    w = trace()[3]
    assert tracing.op_kind(w.name) == "while"


def test_leaves_drop_the_loop():
    names = [tracing.op_name(e.name) for e in tracing.leaves(trace()[3:])]
    assert "while.3" not in names and len(names) == 4


def test_busy_idle_and_ops():
    r = tracing.reduce(trace())
    assert r["window_s"] == pytest.approx(100e-6)
    # busy: [10,20] [25,45] [45,50] [55,85] -> 10 + 25 + 30 = 65 us
    assert r["busy_s"] == pytest.approx(65e-6)
    assert r["devices"] == 1
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(15e-6)
    assert ops["copy-start.1"] == pytest.approx(30e-6)


def test_loop_runs_count_cut_runs_by_their_share():
    ev = trace() + [
        Event(DEV, "XLA Ops", "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
              90e3, 20e3)]                  # half of this run is in the window
    [(name, runs, text)] = tracing.reduce(ev)["loops"]
    assert name == "while.3" and runs == pytest.approx(1.5)
    assert "f32[8]" in text


def test_idle_gaps_by_host_span():
    gaps = dict(tracing.reduce(trace())["idle_gaps"])
    # idle: [0,10] (tick), [20,25] (tick), [50,55] (no span), [85,100] (idle)
    assert gaps["bench.serve.tick"] == pytest.approx(15e-6)
    assert gaps["bench.serve.idle"] == pytest.approx(15e-6)
    assert gaps["no host span"] == pytest.approx(5e-6)


def test_two_chips_average():
    ev = trace() + [op("fusion.9", "fusion", 0, 100, plane="/device:TPU:1")]
    r = tracing.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((65e-6 + 100e-6) / 2)
    assert r["loops"][0][1] == pytest.approx(0.5)     # one run, on one chip of two


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce(trace()[1:])


def _reader(name):
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_module(os.path.join(root, "metrics", name + ".py"), "m_" + name).read


POP_LOOPS = [["while.46", 0.5, "%while.46 = (s32[], f32[800,1000]{1,0}, f32[2000]{0})"],
             ["while.47", 100.0, "%while.47 = (s32[], f32[1000]{0}, f32[800,1000]{1,0})"],
             ["while.48", 1000.0, "%while.48 = (s32[], f32[800,1000]{1,0}, f32[800]{0})"],
             ["while.9", 8000.0, "%while.9 = (s32[], u32[2]{0})"]]


def _rec(busy_s, devices, islands, loops=POP_LOOPS, chunked=True):
    return {"driver": "solve", "peak": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"window_s": 1.0, "busy_s": busy_s, "devices": devices,
                      "loops": loops},
            "solve": {"gens_per_s": 1000.0, "fn": "shifted_rosenbrock",
                      "pop": 800, "dim": 1000, "islands": islands,
                      "chunked": chunked}}


def test_roofline_arithmetic():
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    nbytes, ops = work.generation("shifted_rosenbrock", 800, 1000)
    assert nbytes == 2 * 800 * 1000 * 4 + 2 * 800 * 4
    assert ops == 800 * 1000 * 10
    least = work.least_time("shifted_rosenbrock", 800, 1000, 1, peak)
    assert least == pytest.approx(nbytes / 819e9)          # bound by bytes
    # 1000 generations (runs of the innermost population loop; the loop
    # without the population is not one), device busy half of a 1 s window
    rec = _rec(0.5, 1, 1)
    share = _reader("gen_roofline_share.solve")(rec)
    assert share == pytest.approx(100 * least * 1000 / 0.5)
    assert _reader("device_idle_share.solve")(rec) == pytest.approx(50.0)


def test_roofline_finds_nothing_without_its_loop():
    read = _reader("gen_roofline_share.solve")
    assert read(_rec(0.5, 1, 1, loops=POP_LOOPS[3:])) is None
    assert read(_rec(0.5, 1, 1, chunked=False)) is None


def test_two_chips_roofline_counts_each_chips_islands():
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = work.least_time("shifted_rosenbrock", 800, 1000, 2, peak)
    rec = _rec(1.0, 4, 8)
    assert _reader("gen_roofline_share.solve")(rec) == pytest.approx(100 * least * 1000)


def test_unknown_device_kind_is_an_error():
    from bench import peaks
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
