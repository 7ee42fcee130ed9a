"""The engine's own stages as the benchmark reads them: the counter
readers, the split of device idle time over the program's host spans,
and the output bytes of an HLO instruction."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import stages, trace  # noqa: E402

MS = 1_000_000   # ns per ms


def events():
    """One chip over a 100 ms window, busy 0-10, 40-45 and 90-100 ms, so
    idle 10-40 and 45-90.  Benchmark spans: engine_step 0-60, stamp
    60-70, idle_wait 70-100.  Program spans, nested as the engine nests
    them: step 0-60 > pass 5-55 > dispatch 5-20, sync 20-35, apply 35-50."""
    devices = {"/device:TPU:0": [("fusion.1", 0, 10 * MS),
                                 ("copy.2", 40 * MS, 45 * MS),
                                 ("fusion.1", 90 * MS, 100 * MS)]}
    spans = [(trace.WINDOW_SPAN, 0, 100 * MS),
             ("bench.engine_step", 0, 60 * MS),
             ("bench.stamp", 60 * MS, 70 * MS),
             ("bench.idle_wait", 70 * MS, 100 * MS)]
    program = [("repro.serve.step", 0, 60 * MS),
               ("repro.serve.pass", 5 * MS, 55 * MS),
               ("repro.serve.dispatch", 5 * MS, 20 * MS),
               ("repro.serve.sync", 20 * MS, 35 * MS),
               ("repro.serve.apply", 35 * MS, 50 * MS)]
    return trace.Events(devices, spans), program


def test_idle_split_over_the_innermost_program_span():
    """Each idle stretch goes to the innermost span over it, split exactly
    where spans meet: idle 10-40 is 10 ms of dispatch, 15 of sync and 5
    of apply; 45-90 is 5 of apply, 5 of pass, 5 of step, and 30 ms under
    no program span, labelled by the benchmark's span at its midpoint."""
    ev, program = events()
    out = stages.split_idle(ev, program)
    got = {k: round(v * 1e3, 6) for k, v in
           out["idle_by_program_span"].items()}
    assert got == {"repro.serve.dispatch": 10.0, "repro.serve.sync": 15.0,
                   "repro.serve.apply": 10.0, "repro.serve.pass": 5.0,
                   "repro.serve.step": 5.0, "bench.idle_wait": 30.0}
    assert sum(got.values()) == pytest.approx(75.0)   # all the idle time
    assert out["idle_in_engine_s"] == pytest.approx(0.045)


def test_idle_outside_every_span_keeps_its_benchmark_label():
    ev, program = events()
    out = stages.split_idle(ev, [])
    assert out["idle_by_program_span"] == {         # whole, by midpoint:
        "bench.engine_step": pytest.approx(0.030),   # 10-40 at 25 ms
        "bench.stamp": pytest.approx(0.045)}         # 45-90 at 67.5 ms
    assert out["idle_in_engine_s"] == 0.0
    ev.spans = [s for s in ev.spans if s[0] == trace.WINDOW_SPAN]
    assert stages.split_idle(ev, [])["idle_by_program_span"] == {
        "none": pytest.approx(0.075)}
    ev.spans = []                                    # no traced window
    assert stages.split_idle(ev, program) is None


def test_innermost_timeline_of_nested_and_sibling_spans():
    spans = [("a", 0, 10), ("b", 2, 8), ("c", 2, 4), ("d", 4, 6),
             ("e", 12, 14)]
    assert stages.innermost(spans) == [("a", 0, 2), ("c", 2, 4),
                                       ("d", 4, 6), ("b", 6, 8),
                                       ("a", 8, 10), ("e", 12, 14)]


def test_reduce_keeps_its_keys_beside_program_spans():
    """``trace.reduce`` reads the benchmark's spans only: the program's
    spans beside them change none of its numbers."""
    ev, program = events()
    before = trace.reduce(ev)
    stages.split_idle(ev, program)
    assert trace.reduce(ev) == before
    assert [g[0] for g in before["idle_gaps"]] == ["stamp", "engine_step"]


def test_trace_file_program_spans_read_back(tmp_path):
    """Spans recorded here come back from the ``.xplane.pb``: the engine's
    under ``bench.stages``, the benchmark's under ``trace.Events``."""
    import jax
    import jax.numpy as jnp
    from bench.drivers.common import span
    from repro.serve.engine import span as engine_span

    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with span("engine_step"):
            with engine_span("step"):
                with engine_span("sync"):
                    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    program = stages.read_program_spans(path)
    assert sorted(n for n, _, _ in program) == ["repro.serve.step",
                                                "repro.serve.sync"]
    (_, s0, e0), (_, s1, e1) = sorted(program, key=lambda t: t[1])
    assert s0 <= s1 <= e1 <= e0
    names = {n for n, _, _ in trace.Events.from_file(path).spans}
    assert names == {trace.WINDOW_SPAN, "bench.engine_step"}


def _record(c0, c1, traced=None):
    return {"counters0": {"engine": c0, "pool": {}},
            "counters1": {"engine": c1, "pool": {}},
            "counters_traced": traced}


def test_stage_counter_readers():
    c0 = {"admissions": 4, "queue_wait_s": 2.0, "first_tokens": 3,
          "prefill_s": 6.0, "passes": 100, "host_s": 0.8, "sync_s": 4.0}
    c1 = {"admissions": 14, "queue_wait_s": 7.0, "first_tokens": 11,
          "prefill_s": 22.0, "passes": 300, "host_s": 2.4, "sync_s": 12.0}
    rec = _record(c0, c1)
    assert stages.queue_wait_ms(rec) == pytest.approx(500.0)
    assert stages.prefill_ms(rec) == pytest.approx(2000.0)
    assert stages.host_ms_per_pass(rec) == pytest.approx(8.0)
    # the traced stretch is left out, as in bench.readers
    t0 = dict(c0, passes=150, host_s=1.0)
    t1 = dict(c0, passes=250, host_s=1.8)
    rec = _record(c0, c1, [{"engine": t0, "pool": {}},
                           {"engine": t1, "pool": {}}])
    assert stages.host_ms_per_pass(rec) == pytest.approx(8.0)
    # nothing moved, or an engine without the stage counters: no number
    assert stages.prefill_ms(_record(c0, c0)) is None
    old = {"admissions": 4, "passes": 100}
    assert stages.queue_wait_ms(_record(old, dict(old, passes=200))) is None
    assert stages.host_ms_per_pass(_record(old, dict(old, passes=200))) \
        is None


@pytest.mark.parametrize("metric,reader", [
    ("queue_wait_ms.chat", stages.queue_wait_ms),
    ("prefill_ms.chat", stages.prefill_ms),
    ("host_ms_per_pass.chat", stages.host_ms_per_pass),
    ("host_ms_per_pass.batch", stages.host_ms_per_pass)])
def test_metric_files_name_the_stage_readers(metric, reader):
    from bench import run
    assert run.load_reader(metric) is reader
    bench = run.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["source"] == "program_counter"


@pytest.mark.parametrize("text,nbytes", [
    ("%copy.3 = bf16[10,512]{1,0} copy(bf16[10,512]{0,1} %p)", 10240),
    ("copy.7 = bf16[320,16,20,128]{3,2,1,0:T(8,128)(2,1)} copy(%a)",
     320 * 16 * 20 * 128 * 2),
    ("%t = (f32[4]{0}, s32[]) tuple(%a, %b)", 16 + 4),
    ("%c = pred[] constant(false)", 1),
    ("fusion.12", None)])
def test_output_bytes_from_hlo_text(text, nbytes):
    assert stages.output_bytes(text) == nbytes


def test_stage_tool_reads_the_entry_computation_of_tpu_hlo():
    """``tools/serve_stages.py`` lists a compiled step's instructions from
    its HLO text as the TPU compiler prints it (tiled layouts, memory
    spaces, tuple results), and names scopes without layer indices."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve_stages

    hlo = "\n".join([
        "%fused_computation.1 (p: bf16[4]) -> bf16[4] {",
        "  ROOT %copy.9 = bf16[4]{0} copy(bf16[4]{0} %p)",
        "}",
        "ENTRY %main.5 (p0: bf16[320,16,20,128]) -> bf16[5120,20,128] {",
        '  %copy.2 = bf16[5120,20,128]{2,1,0:T(8,128)(2,1)S(1)} copy('
        '%bitcast.1), metadata={op_name="jit(step)/layer7/attn/kv_write/'
        'reshape" stack_frame_id=74}',
        "  %copy-start.3 = (bf16[40,2560]{1,0}, bf16[40,2560]{1,0}, u32[]) "
        "copy-start(%p1)",
        '  ROOT %fusion.4 = f32[10]{0} fusion(%copy.2), kind=kLoop, '
        'metadata={op_name="jit(step)/head/dot_general"}',
        "}"])
    got = serve_stages.entry_instructions(hlo)
    assert got == [
        ("copy.2", "copy", "jit(step)/layer7/attn/kv_write/reshape",
         5120 * 20 * 128 * 2, "bf16[5120,20,128]@S1"),
        ("copy-start.3", "copy-start", "", 2 * 40 * 2560 * 2 + 4,
         "(bf16[40,2560], bf16[40,2560], u32[])"),
        ("fusion.4", "fusion", "jit(step)/head/dot_general", 40,
         "f32[10]")]
    assert serve_stages.scope(got[0][2]) == "layer*/attn/kv_write/reshape"
    assert serve_stages.scope("caches[12]['k']") == "caches[*]['k']"
    assert serve_stages.scope("") == "(none)"
