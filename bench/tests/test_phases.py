"""The per-scope reduction of ``bench/phases.py`` on a small synthetic trace."""
import pytest

from bench import phases, tracing
from bench.tracing import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
RUN = "jit(run)/while/body/closed_call/"


def op(name, kind, start_us, dur_us, path=""):
    return phases.Op(DEV, "XLA Ops",
                     f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p)",
                     start_us * 1e3, dur_us * 1e3, path, phases.innermost(path))


def trace():
    """A 100 us window: one run of the round's generation scan holding a
    chunk loop and six leaf ops of four phases and none; the solving thread
    waits in ``popt.engine.fetch`` until 80 us, and the capture thread's
    Python-tracer sleep covers the whole window."""
    gen = RUN + "popt.round/while/body/closed_call/"
    return [
        Event(HOST, "python", tracing.WINDOW_SPAN, 0.0, 100e3),
        Event(HOST, "python", "popt.engine.dispatch", 0.0, 5e3),
        Event(HOST, "python", "popt.engine.fetch", 5e3, 75e3),
        Event(HOST, "bench-trace", "_time_sleep", 0.0, 99e3),
        op("while.2", "while", 10, 80, RUN + "popt.round/while"),
        op("while.3", "while", 12, 76, gen + "while"),
        op("fusion.1", "fusion", 12, 20, gen + "popt.variation/jit(_uniform)/or"),
        op("fusion.2", "fusion", 35, 10, gen + "popt.evaluate/vmap()/reduce_sum"),
        op("fusion.3", "fusion", 45, 10, gen + "popt.retry/vmap()/reduce_sum"),
        op("fusion.4", "fusion", 55, 5, gen + "popt.select/dynamic_update_slice"),
        op("copy-done.1", "copy-done", 60, 10),
        op("fusion.5", "fusion", 70, 5, gen + "vmap(popt.variation)/gather"),
    ]


@pytest.mark.parametrize("path,scope", [
    (RUN + "popt.round/while/body/popt.variation/jit(_uniform)/add",
     "popt.variation"),
    (RUN + "popt.round/while/body/vmap(popt.select)/select_n", "popt.select"),
    (RUN + "cond/branch_1_fun/vmap(popt.polish)/popt.evaluate/add",
     "popt.evaluate"),
    ("jit(run)/while", phases.UNSCOPED),
    ("", phases.UNSCOPED),
])
def test_innermost_scope(path, scope):
    assert phases.innermost(path) == scope


def _pb(*fields) -> bytes:
    """A protobuf message from ``(field number, int | bytes | str)`` pairs."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def _plane(name, stat_names, metadata):
    """An ``XPlane`` with its stat names and its event metadata, each
    ``(name, [(stat name, int or bytes)])``."""
    ids = {n: i + 1 for i, n in enumerate(stat_names)}
    fields = [(2, name)]
    fields += [(5, _pb((1, i), (2, _pb((1, i), (2, n))))) for n, i in ids.items()]
    for k, (md_name, stats) in enumerate(metadata, start=100):
        st = [(5, _pb((1, ids[n]), (3, v) if isinstance(v, int) else (6, v)))
              for n, v in stats]
        fields.append((4, _pb((1, k), (2, _pb((1, k), (2, md_name), *st)))))
    return _pb(*fields)


def _hlo(*insts):
    comp = _pb(*[(2, _pb((1, n), (7, _pb((2, p))))) for n, p in insts])
    return _pb((1, _pb((1, "jit_run"), (3, comp))))


def test_op_names_come_from_the_programs_the_trace_holds(tmp_path):
    """A device op's event metadata names its program; the program's HLO
    proto, on the ``/host:metadata`` plane, holds its ``op_name``. A name two
    programs give ops of different ``op_name`` reads ``""``."""
    sel, ev = RUN + "popt.select/le", RUN + "popt.evaluate/add"
    f1 = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
    f2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)"
    big = 17912796627301604343                  # program ids use all 64 bits
    meta = _plane("/host:metadata", ["Hlo Proto"], [
        (f"jit_run({big})", [("Hlo Proto", _hlo(("fusion.1", sel),
                                                 ("fusion.2", ev)))]),
        ("jit_step(7)", [("Hlo Proto", _hlo(("fusion.2", sel)))])])
    dev = _plane(DEV, ["hlo_category", "program_id"], [
        (f1, [("hlo_category", b"loop fusion"), ("program_id", big)]),
        (f2, [("program_id", big)]),
        (f2, [("program_id", 7)]),
        ("%copy-done.1 = f32[8]{0} copy-done(f32[8]{0} %p)",
         [("program_id", 9)])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, dev), (1, _plane("/host:CPU", [], [])),
                         (1, meta)))
    names = phases.op_names(str(path))
    assert names[f1] == sel and names[f2] == ""
    assert names["%copy-done.1 = f32[8]{0} copy-done(f32[8]{0} %p)"] == ""


def test_scopes_count_leaf_time_and_ops():
    sc = phases.scopes(trace(), 0.0, 100e3)
    assert sc["popt.variation"] == pytest.approx([25e-6, 2])
    assert sc["popt.evaluate"] == pytest.approx([10e-6, 1])
    assert sc["popt.retry"] == pytest.approx([10e-6, 1])
    assert sc["popt.select"] == pytest.approx([5e-6, 1])
    assert sc[phases.UNSCOPED] == pytest.approx([10e-6, 1])
    assert "popt.round" not in sc                    # the loops are not leaves


@pytest.mark.parametrize("path,runs", [
    (RUN + "popt.round/while", 1.5),                 # plus half a run cut
    ("jit(many)/vmap(popt.round)/while", 1.5),       # a jobs axis's round
    (RUN + "popt.round/while/body/closed_call/while", 1.0),   # a chunk loop
    (RUN + "popt.round/jit(_threefry_split)/f/while", 1.0),
])
def test_generations_are_runs_of_the_loop_directly_under_the_round(path, runs):
    ev = trace() + [op("while.9", "while", 90, 20, path)]
    assert phases.round_loop_runs(ev, 0.0, 100e3) == pytest.approx(runs)


def test_per_generation_phases_sum_to_busy_time():
    r = phases.reduce(trace(), sync_every=10)
    assert r["generations"] == pytest.approx(10)
    # busy: [12,32] [35,60] [60,75] -> 60 us, as tracing.reduce counts it
    assert r["busy_s"] == pytest.approx(tracing.reduce(trace())["busy_s"])
    assert r["busy_s"] == pytest.approx(60e-6)
    [per] = r["per_generation"]
    assert per["generations"] == 10 and per["span_s"] == pytest.approx(80e-6)
    assert per["variation_us"] == pytest.approx(2.5)
    assert per["eval_us"] == pytest.approx(1.0)
    assert per["retry_eval_us"] == pytest.approx(1.0)
    assert per["select_us"] == pytest.approx(0.5)
    assert per["rest_us"] == pytest.approx(1.0)
    assert per["by_scope_us"][phases.UNSCOPED] == pytest.approx(1.0)
    assert per["busy_us"] == pytest.approx(6.0)
    assert per["leaf_sum_us"] == pytest.approx(6.0)
    assert per["ops_per_gen"] == pytest.approx(0.6)
    assert per["loop_runs"] == {"while.2": 1.0, "while.3": 1.0}


def test_per_generation_numbers_come_from_whole_rounds_only():
    """A round scan cut by an edge of the window counts towards the
    window's generations by its share, but not towards the numbers per
    generation: its ops are only partly in the trace."""
    late = RUN + "popt.round/while/body/closed_call/"
    ev = trace() + [op("while.2", "while", 90, 20, RUN + "popt.round/while"),
                    op("fusion.6", "fusion", 92, 6, late + "popt.select/le")]
    r = phases.reduce(ev, sync_every=10)
    assert r["generations"] == pytest.approx(15)
    [per] = r["per_generation"]
    assert per["generations"] == 10
    assert per["select_us"] == pytest.approx(0.5)


def test_no_generations_no_per_generation_numbers():
    ev = [e for e in trace() if not e.name.startswith("%while.2")]
    r = phases.reduce(ev, sync_every=10)
    assert r["generations"] == 0 and r["per_generation"] == []


def test_idle_gaps_go_to_program_spans_never_to_another_thread():
    gaps = dict(phases.reduce(trace(), sync_every=10)["idle_gaps"])
    # idle: [0,12] and [32,35] in popt.engine.fetch; [75,100] outside it,
    # where only the capture thread's sleep runs
    assert gaps == pytest.approx({"popt.engine.fetch": 15e-6,
                                  phases.NO_SPAN: 25e-6})
    assert "_time_sleep" in dict(tracing.reduce(trace())["idle_gaps"])


def test_the_probe_runs_end_to_end_off_the_chip():
    """The command's whole path on the CPU at a test size: the CPU's trace
    has no device plane, so nothing is put down to a scope there."""
    from bench.tests.test_faults import ROOT, tiny_bench
    out = phases.probe(ROOT, "table1.de_chunked", 3, require_chip=False,
                       bench=tiny_bench())
    assert out["problem_wall_s"]["traced"] > 0
    assert out["phases"]["devices"] == 0 and out["phases"]["generations"] == 0
    assert set(out["accepted"]) == {"device_idle_share.solve",
                                    "gen_roofline_share.solve"}
