"""The reduction by the program's own names (``bench/harness/spans.py``):
on hand-made profiles, and on a profile of a small paged engine recorded
here on the CPU."""
import types

import jax
import numpy as np
import pytest

from bench.harness import spans as sp
from bench.harness import trace as tr


def host(name, s, t, thread=0, **args):
    return sp.HostEvent(name, float(s), float(t), thread=thread,
                        args={k: str(v) for k, v in args.items()})


def op(s, t, op_name=""):
    return sp.DeviceOp("fusion.1", float(s), float(t), op_name=op_name)


def profile(dev, host_events, programs=()):
    return sp.SpanProfile(devices={"/device:TPU:0": dev},
                          host=sorted(host_events, key=lambda e: e.start_ns),
                          programs={"/device:TPU:0": list(programs)})


def test_idle_goes_to_the_innermost_span_on_the_engine_thread():
    dev = [op(0, 10), op(40, 50), op(70, 100)]
    hosts = [host(tr.WINDOW_SPAN, 0, 100, thread=1),
             host("engine.step", 5, 60), host("engine.decode", 8, 45),
             host("engine.decode.fetch", 20, 42),
             host("service.wait", 62, 75),
             # another thread's span never takes the engine's idle
             host("service.submit", 0, 100, thread=1)]
    prof = profile(dev, hosts)
    idle = sp.idle_by_span(prof, 0, 100)
    assert idle == {
        "engine.decode": pytest.approx(10e-9),        # 10..20
        "engine.decode.fetch": pytest.approx(20e-9),  # 20..40
        "engine.step": pytest.approx(10e-9),          # 50..60
        "none": pytest.approx(2e-9),                  # 60..62
        "service.wait": pytest.approx(8e-9),          # 62..70
    }
    busy = tr.busy_seconds(prof, 0, 100)
    assert sum(idle.values()) == pytest.approx(100e-9 - busy)


def test_innermost_pieces_cover_the_window_in_order():
    spans = [host("engine.step", 10, 90), host("engine.admit", 10, 20),
             host("engine.decode", 30, 95), host("engine.emit", 60, 70)]
    # engine.decode outlives its parent's clip at the window's end
    pieces = sp.innermost(spans, 0, 80)
    assert pieces == [(0, 10, "none"), (10, 20, "engine.admit"),
                      (20, 30, "engine.step"), (30, 60, "engine.decode"),
                      (60, 70, "engine.emit"), (70, 80, "engine.decode")]


def test_scopes_group_own_time_by_innermost_scope():
    step = "jit(_step)/decode_step"
    dev = [
        op(0, 100, f"{step}/layers/while"),
        op(10, 30, f"{step}/layers/while/body/attn.kv_append/scatter"),
        op(30, 40, f"{step}/layers/while/body/dynamic_slice"),
        op(40, 60, f"{step}/layers/while/body/attn.core/pallas_call"),
        op(60, 70, f"{step}/layers/while/body/mlp/dot_general"),
        op(100, 110, f"{step}/final/dot_general"),
        op(110, 115, ""),
        op(115, 120, "jit(_step)/argmax"),
    ]
    prof = profile(dev, [host(tr.WINDOW_SPAN, 0, 200)])
    scopes = sp.scope_seconds(prof, 0, 200)
    assert scopes == {
        "layers": pytest.approx(50e-9),     # the loop's own 40 + slice 10
        "attn.kv_append": pytest.approx(20e-9),
        "attn.core": pytest.approx(20e-9),
        "mlp": pytest.approx(10e-9),
        "final": pytest.approx(10e-9),
        sp.NO_SCOPE: pytest.approx(10e-9),
    }
    assert sum(scopes.values()) == pytest.approx(
        tr.busy_seconds(prof, 0, 200))


def test_scope_of_takes_the_innermost_known_name():
    assert sp.scope_of("jit(f)/decode_step/layers/while/body/attn.core/"
                       "attn.core/pallas_call") == "attn.core"
    assert sp.scope_of("jit(f)/decode_step/layers/moe/mlp/dot") == "mlp"
    assert sp.scope_of("jit(f)/while/body/add") == sp.NO_SCOPE
    assert sp.scope_of("") == sp.NO_SCOPE


def test_operations_take_the_op_name_of_their_programs_instruction():
    """A TPU operation event is named after its instruction and lies in
    its program's execution: the compiled HLO of that program (of the
    variant with its result shape) gives its op_name."""
    def text(shape, scope):
        return ('HloModule jit__step, entry_computation_layout={}\n'
                f'  %fusion.3 = {shape} fusion(%p), kind=kLoop, '
                f'metadata={{op_name="jit(_step)/decode_step/{scope}/mul" '
                'stack_frame_id=2}\n'
                '  ROOT %copy.1 = (f32[2]{0}, u32[]{:S(2)}) copy(%fusion.3), '
                'metadata={op_name="jit(_step)/decode_step/final/copy"}\n')

    # two variants of one program: one name, two shapes, two scopes
    names = sp.op_names_from_hlo([text("f32[2]{0:T(128)}", "layers"),
                                  text("f32[4]{0:T(128)}", "mlp")])
    assert names[("jit__step", "fusion.3", "f32[2]{0:T(128)}")].endswith(
        "/layers/mul")
    assert names[("jit__step", "copy.1", "(f32[2]{0}, u32[]{:S(2)})")]\
        .endswith("final/copy")
    programs = [tr.Event("jit__step", 0, 50), tr.Event("jit__step", 60, 90)]
    ops = [(None, 5, 10), (None, 62, 70), (None, 70, 80), (None, 95, 99)]
    heads = ["%fusion.3 = f32[2]{0:T(128)} fusion(f32[8]{0} %p)",
             "%fusion.3 = f32[4]{0:T(128)} fusion(f32[8]{0} %p)",
             "%copy.1 = (f32[2]{0}, u32[]{:S(2)}) copy(f32[2]{0} %fusion.3)",
             "%fusion.3 = f32[2]{0:T(128)} fusion(f32[8]{0} %p)"]
    ops = [(types.SimpleNamespace(name=h), s, t)
           for h, (_, s, t) in zip(heads, ops)]
    named = sp._named_ops(ops, programs, names)
    assert [sp.scope_of(o.op_name) for o in named] == [
        "layers", "mlp", "final", sp.NO_SCOPE]   # the last: no program


def test_readers_find_nothing_in_a_profile_without_the_programs_names():
    # a trace of the program before its spans and scopes: the span and
    # scope readers find nothing; the programs' executions are there
    dev = [op(0, 50, "jit(_step)/while/body/add"), op(60, 90)]
    prof = profile(dev, [host(tr.WINDOW_SPAN, 0, 100),
                         host("np.asarray(jax.Array)", 50, 60)],
                   [tr.Event("jit__step", 0, 50),
                    tr.Event(sp.CHUNK_PROGRAM, 60, 90)])
    summary = sp.summarize(prof)
    assert summary["spans"] == {} and summary["idle_by_span"] == {}
    assert set(summary["scopes"]) == {sp.NO_SCOPE}
    got = {k: f(summary) for k, f in sp.METRICS.items()}
    assert got.pop("prefill_chunk_ms.itl_p95_ms") == pytest.approx(30e-6)
    assert got == dict.fromkeys(got)
    assert all(f(None) is None for f in sp.METRICS.values())
    assert all(f({"window_s": 1.0}) is None for f in sp.METRICS.values())


def test_readers_on_a_traced_window():
    dev = [op(0, 30, "jit(_step)/decode_step/layers/while/body/copy"),
           op(30, 40, "jit(_step)/decode_step/layers/attn.core/x"),
           op(60, 70, "jit(_chunk_step)/prefill_chunk/layers/mlp/dot")]
    hosts = [host(tr.WINDOW_SPAN, 0, 100, thread=1),
             host("engine.step", 0, 58, step=1),
             host("engine.decode", 2, 50, active=3, mb=4),
             host("engine.decode.fetch", 40, 45),
             host("engine.emit", 45, 50, tokens=3),
             host("engine.step", 58, 99, step=2),
             host("engine.prefill", 58, 75, T=64, rows=2),
             host("engine.decode", 75, 99, active=3, mb=4),
             host("service.take", 99, 100, taken=0)]
    programs = [tr.Event("jit__step", 0, 40),
                tr.Event(sp.CHUNK_PROGRAM, 60, 70),
                tr.Event(sp.CHUNK_PROGRAM, 90, 104)]   # ends past the window
    summary = sp.summarize(profile(dev, hosts, programs))
    got = {k: f(summary) for k, f in sp.METRICS.items()}
    assert got["decode_step_ms.serve_tok_s"] == pytest.approx(36e-6)
    # the chunk program's device time, not the engine.prefill span's
    assert got["prefill_chunk_ms.itl_p95_ms"] == pytest.approx(10e-6)
    # idle 40..60 and 70..100: all host work but decode.fetch's 40..45
    host_idle = (5 + 8 + 2 + 5 + 24 + 1) / 100
    assert got["idle_host.serve_tok_s"] == pytest.approx(100 * host_idle)
    assert got["layer_loop_overhead.serve_tok_s"] == pytest.approx(30.0)


def test_span_tree_of_a_small_paged_engine(tmp_path):
    """A paged engine's steps under the profiler on the CPU: one
    ``engine.decode`` and one ``engine.decode.fetch`` per decode step, the
    fetch inside the decode, and the ``rid`` of each request linking its
    ``service.submit`` to its ``engine.admit``."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.core.task import ServiceControl
    from repro.serve import Request, ServeEngine

    eng = ServeEngine(get_config("tinyllama-1.1b", smoke=True), RunConfig(),
                      max_slots=2, max_len=32, prefill_chunk_tokens=8)
    control = ServiceControl()
    rng = np.random.default_rng(3)
    reqs = [Request(rng.integers(1, 100, n).astype(np.int32),
                    max_new_tokens=3) for n in (5, 12, 7)]
    for r in reqs:      # compile outside the trace
        eng.submit(r)
    eng.run_until_drained()
    reqs = [Request(r.prompt, max_new_tokens=3) for r in reqs]
    eng.reset_stats()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for r in reqs:
            control.submit_request(r)
        control.drain()
        eng.run_service(control)
    jax.profiler.stop_trace()
    prof = sp.read_spans(tr.find_profile(str(tmp_path)))
    lo, hi = prof.window()
    spans = [e for e in prof.host if e.name.startswith(sp.PROGRAM_SPANS)]
    by = {}
    for e in spans:
        by.setdefault(e.name, []).append(e)
    stats = eng.stats()
    decodes = by["engine.decode"]
    assert len(decodes) == len(by["engine.decode.fetch"]) \
        == stats["decode_steps"] > 0
    # a chunk fetches first tokens only where a prompt finishes in it
    assert len(by["engine.prefill"]) >= len(by["engine.prefill.fetch"]) > 0
    assert len(by["engine.emit"]) == len(decodes)
    for d, f in zip(decodes, by["engine.decode.fetch"]):
        assert d.start_ns <= f.start_ns <= f.end_ns <= d.end_ns
        assert set(d.args) >= {"active", "sampling", "mb"}
    steps = by["engine.step"]
    assert [int(s.args["step"]) for s in steps] == sorted(
        int(s.args["step"]) for s in steps)
    for d in decodes:   # every decode inside one step, on its thread
        assert sum(s.start_ns <= d.start_ns and d.end_ns <= s.end_ns
                   and s.thread == d.thread for s in steps) == 1
    assert {p.args["T"] for p in by["engine.prefill"]} <= {"2", "4", "8"}
    submitted = [e.args["rid"] for e in by["service.submit"]]
    admitted = " ".join(e.args["rids"] for e in by["engine.admit"]
                        if "rids" in e.args).split()
    assert submitted == [r.rid for r in reqs]
    assert sorted(admitted) == sorted(submitted)
    finished = " ".join(e.args.get("finished", "")
                        for e in by["engine.emit"]).split()
    assert sorted(finished) == sorted(submitted)
    assert sum(int(e.args["tokens"]) for e in by["engine.emit"]) \
        == sum(len(r.tokens) - 1 for r in reqs)
    summary = sp.summarize(prof)
    assert lo < hi and summary["spans"]["engine.decode"]


def test_traced_window_reports_the_span_metrics(monkeypatch, smoke_root):
    """``bench/trace_spans.py`` on the serving cell at smoke size: the
    span metric reads the window's spans."""
    import importlib.util

    from bench.harness import checkout, manifest
    from bench_smoke import REPO, SERVE_CELL

    monkeypatch.setattr(checkout, "setup_process", lambda: None)
    monkeypatch.setattr(checkout, "enable_cache", lambda: None)
    spec = importlib.util.spec_from_file_location(
        "bench_trace_spans_script", REPO / "bench" / "trace_spans.py")
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    cell = manifest.find_cell(SERVE_CELL, smoke_root)
    line = script.report(script.traced_window(cell, 2**33 + 11, 2.0,
                                              jax.devices()))
    assert line["correct"] is True
    assert set(line["metrics"]) == set(sp.METRICS)
    assert line["metrics"]["decode_step_ms.serve_tok_s"] > 0
    # the CPU has no device plane: no program, idle or scope time
    assert line["metrics"]["prefill_chunk_ms.itl_p95_ms"] is None
    assert line["metrics"]["idle_host.serve_tok_s"] is None
    assert line["metrics"]["layer_loop_overhead.serve_tok_s"] is None
    assert line["programs"] == {}
    spans = line["spans"]
    assert spans["engine.decode"]["n"] == spans["engine.decode.fetch"]["n"]
    assert spans["engine.step"]["n"] >= spans["engine.decode"]["n"] > 0
    assert spans["service.submit"]["n"] > 0
    assert line["e2e"]["serve_tok_s"] > 0


def test_hlo_of_the_warmed_steps_maps_operations_to_scopes(monkeypatch,
                                                           smoke_root):
    """Where a trace's operations carry no op_name, the span script maps
    them through the HLO of the step programs the cell warms."""
    import importlib.util

    from bench.harness import checkout, manifest
    from bench_smoke import REPO, SERVE_CELL

    monkeypatch.setattr(checkout, "setup_process", lambda: None)
    spec = importlib.util.spec_from_file_location(
        "bench_trace_spans_script", REPO / "bench" / "trace_spans.py")
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    cell = manifest.find_cell(SERVE_CELL, smoke_root)
    names = sp.op_names_from_hlo(script.hlo_texts(cell, 5))
    scopes = {sp.scope_of(n) for n in names.values()}
    assert {"decode_step", "prefill_chunk", "layers", "attn.kv_append",
            "attn.core", "mlp"} <= scopes
    assert {k[0] for k in names} >= {"jit__step", "jit_chunk_step"}
