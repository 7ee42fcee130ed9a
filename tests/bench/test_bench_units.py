"""The benchmark's yardstick on the CPU: trace reduction, operation and
byte counts, the traffic generator and the metric arithmetic."""

import os
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import counts, gen, readers, stats, trace  # noqa: E402

MS = 1_000_000   # ns per ms


# ------------------------------------------------------------------ trace

def recorded_trace():
    """Two chips over a 100 ms window.  Chip 0: compute 0-30, an
    all-reduce 25-45 (exposed 30-45), compute 60-90.  Chip 1: compute
    12-50 and an all-gather-done 50-55.  Host: engine_step 0-50, submit
    50-70, idle_wait 70-100."""
    devices = {
        "/device:TPU:0": [("fusion.1", 0, 30 * MS),
                          ("all-reduce.3", 25 * MS, 45 * MS),
                          ("fusion.1", 60 * MS, 90 * MS)],
        "/device:TPU:1": [("convolution.2", 12 * MS, 50 * MS),
                          ("all-gather-done.7", 50 * MS, 55 * MS),
                          ("fusion.9", 120 * MS, 130 * MS)],   # after window
    }
    spans = [(trace.WINDOW_SPAN, 0, 100 * MS),
             ("bench.engine_step", 0, 50 * MS),
             ("bench.submit", 50 * MS, 70 * MS),
             ("bench.idle_wait", 70 * MS, 100 * MS)]
    op_bytes = {"/device:TPU:0": [{"bytes_accessed": 100.0}, {},
                                  {"bytes_accessed": 50.0}]}
    return trace.Events(devices, spans, op_bytes)


def test_trace_busy_idle_and_collectives():
    out = trace.reduce(recorded_trace())
    d0, d1 = out["devices"]["/device:TPU:0"], out["devices"]["/device:TPU:1"]
    assert d0["busy_ns"] == 75 * MS            # 0-45 and 60-90
    assert d1["busy_ns"] == 43 * MS            # 12-55; 120-130 is outside
    assert d0["collective_exposed_ns"] == 15 * MS
    assert d1["collective_exposed_ns"] == 5 * MS
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.059)
    assert out["idle_share_max"] == pytest.approx(0.57)
    assert out["collective_share_mean"] == pytest.approx(0.1)
    # breakdown: ops by time (averaged over chips), gaps by host span
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.03)]
    assert [g[0] for g in out["idle_gaps"][:3]] == ["idle_wait", "submit",
                                                    "engine_step"]
    assert out["idle_gaps"][0][1] == pytest.approx(0.045)   # chip 1 55-100
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    # events, seconds and byte statistics per op, over all chips
    assert out["op_stats"]["fusion.1"] == [2, pytest.approx(0.06),
                                           {"bytes_accessed": 150.0}]
    assert out["op_stats"]["convolution.2"] == [1, pytest.approx(0.038), {}]


def test_trace_without_device_work_reads_nothing():
    ev = recorded_trace()
    ev.devices = {k: [] for k in ev.devices}
    assert trace.reduce(ev) is None
    ev = recorded_trace()
    ev.spans = ev.spans[1:]                    # no traced window
    assert trace.reduce(ev) is None


def test_trace_file_read_back(tmp_path):
    """A trace recorded here (the CPU has no TPU plane) is read from its
    ``.xplane.pb``: the benchmark's host spans come back, the window span
    among them, and with no device operation the reduction reads nothing."""
    import jax
    import jax.numpy as jnp
    from bench.drivers.common import span

    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with span("engine_step"):
            f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.Events.from_file(trace.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in ev.spans}
    assert {trace.WINDOW_SPAN, "bench.engine_step"} <= names
    lo, hi = ev.window()
    assert hi > lo
    assert trace.reduce(ev) is None


def test_interval_arithmetic():
    assert trace.union([(5, 9), (0, 3), (2, 6)]) == [(0, 9)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                          (7, 10)]
    assert trace.gaps([(0, 3), (8, 9)], 0, 10) == [(3, 8), (9, 10)]


# ----------------------------------------------------------------- counts

def tiny_dims():
    return counts.DenseDims(d=8, ff=16, layers=2, heads=2, kv_heads=1, hd=4,
                            vocab=32, tied=False, bias=True, layernorm=False)


def test_decode_pass_counts_by_hand():
    dm = tiny_dims()
    # per layer matmul weights: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3*8*16
    assert dm.layer_matmul_params == 64 + 32 + 32 + 64 + 384
    # plus 2 rms scales of 8 and q/k/v biases 8+4+4
    assert dm.layer_params == 576 + 16 + 16
    lanes = [(10, True), (3, False)]
    flops, nbytes = counts.decode_pass(dm, lanes)
    want_flops = (2 * 576 * 2 * 2          # matmuls, 2 layers, 2 lanes
                  + 4 * 2 * 4 * 2 * 13     # attention over 13 live keys
                  + 2 * 8 * 32 * 1)        # head for the one emitting lane
    assert flops == want_flops
    kv_pos = 2 * 2 * 1 * 4 * 2             # k+v, layers, kv heads, hd, bf16
    want_bytes = ((608 * 2 + 8 + 8 * 32) * 2   # weights + final norm + head
                  + 2 * 8 * 2                  # embedding rows
                  + kv_pos * (13 + 2))         # live keys read, 2 written
    assert nbytes == want_bytes
    # no emitting lane: the head is neither computed nor read
    f2, b2 = counts.decode_pass(dm, [(10, False), (3, False)])
    assert want_flops - f2 == 2 * 8 * 32
    assert want_bytes - b2 == 8 * 32 * 2


def test_train_step_counts_by_hand():
    dm = tiny_dims()
    flops, _ = counts.train_step(dm, batch=2, seq=4)
    pairs = 2 * 4 * 5 / 2
    assert flops == 6 * (576 * 2 + 8 * 32) * 8 + 12 * 2 * 4 * 2 * pairs


def test_peaks_table_refuses_unknown_devices():
    assert counts.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")
    t, which = counts.least_seconds(197e12, 819e9 * 2, counts.peaks_for(
        "TPU v5 lite"))
    assert (t, which) == (2.0, "bytes")


# -------------------------------------------------------------- generator

@pytest.mark.parametrize("name", ["chat-prefix", "batch-unshared"])
def test_generator_deterministic_and_clipped(name):
    mix = gen.load_mix(name)
    a = gen.generate(mix, 2 ** 34 + 7, 64, 1000)
    b = gen.generate(mix, 2 ** 34 + 7, 64, 1000)
    c = gen.generate(mix, 11, 64, 1000)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    key = "suffix" if mix.get("prefix") else "prompt"
    pre = mix["prefix"]["tokens"] if mix.get("prefix") else 0
    for r in a:
        assert mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"]
        assert mix[key]["min"] <= len(r.prompt) - pre <= mix[key]["max"]
        assert all(0 <= t < 1000 for t in r.prompt)
        assert len(r.prompt) + r.max_new_tokens < 512     # the engine's max_seq
    # every seed gets the same work at the same times; the words differ
    for x, y in zip(a, c):
        assert (x.due, x.max_new_tokens, x.tenant, x.group, x.repeat_of,
                len(x.prompt)) == (y.due, y.max_new_tokens, y.tenant,
                                   y.group, y.repeat_of, len(y.prompt))
    # a block holds each quantile once
    outs = sorted(r.max_new_tokens for r in a[:mix["block"]])
    assert outs == gen.lognormal_quantiles(mix["output"], mix["block"])
    if mix["kind"] == "open":
        gaps = sorted(y.due - x.due for x, y in zip(a, a[1:mix["block"]]))
        assert all(g >= 0 for g in gaps)
    else:
        assert all(r.due == 0 for r in a)


def test_prefix_groups_as_specified():
    mix = gen.load_mix("chat-prefix")
    reqs = gen.generate(mix, 5, 64, 1000)
    p = mix["prefix"]
    fresh = [r for r in reqs if r.repeat_of < 0]
    heads = {}
    for r in fresh:
        heads.setdefault(r.group, set()).add(tuple(r.prompt[:p["tokens"]]))
    assert sorted(heads) == list(range(p["groups"]))
    assert all(len(v) == 1 for v in heads.values())     # one prompt a group
    assert len({next(iter(v)) for v in heads.values()}) == p["groups"]
    # Zipf 1.5 over 4 groups in a block of 16: 10, 3, 2, 1
    per_block = Counter(r.group for r in gen.generate(
        dict(mix, repeats_per_block=0), 5, 16, 1000))
    assert [per_block[g] for g in range(4)] == [10, 3, 2, 1]
    # repeats copy an earlier request's whole prompt
    reps = [r for r in reqs if r.repeat_of >= 0]
    assert len(reps) == 64 // mix["block"] * mix["repeats_per_block"]
    for r in reps:
        assert r.repeat_of < r.index
        assert r.prompt == reqs[r.repeat_of].prompt


def test_stratified_quantiles():
    spec = {"median": 64, "sigma": 0.6, "min": 16, "max": 160}
    q = gen.lognormal_quantiles(spec, 16)
    assert q == sorted(q) and q[7] <= 64 <= q[8]
    assert gen.share_counts([3, 1], 16) == [12, 4]
    assert sum(gen.exponential_quantiles(2.0, 1000)) == pytest.approx(
        500, rel=0.01)
    assert gen.seed_words(2 ** 40 + 3) == [3, 256]


# ----------------------------------------------------------------- weights

def test_weight_stream_is_seeded_per_leaf_and_layer():
    import jax
    import numpy as np
    from bench import weights

    shapes = {"embed": {"table": jax.ShapeDtypeStruct((64, 8), "float32")},
              "layers": {"mlp": {"up": {"w": jax.ShapeDtypeStruct(
                  (3, 256, 512), "float32")}},
                  "ln1": {"scale": jax.ShapeDtypeStruct((3, 256),
                                                        "float32")}}}
    a = weights.make_params(2 ** 35 + 1, shapes)
    b = weights.make_params(2 ** 35 + 1, shapes)
    c = weights.make_params(7, shapes)
    up = np.asarray(a["layers"]["mlp"]["up"]["w"])
    np.testing.assert_array_equal(up, np.asarray(b["layers"]["mlp"]["up"][
        "w"]))
    assert not np.array_equal(up, np.asarray(c["layers"]["mlp"]["up"]["w"]))
    make = weights.layer_maker(2 ** 35 + 1, {"mlp/up/w": (256, 512),
                                            "ln1/scale": (256,)}, "float32")
    for i in range(3):       # one layer alone equals the stacked copy
        one = make(i)
        np.testing.assert_array_equal(np.asarray(one["mlp/up/w"]), up[i])
        np.testing.assert_array_equal(
            np.asarray(one["ln1/scale"]),
            np.asarray(a["layers"]["ln1"]["scale"])[i])
    assert not np.array_equal(up[0], up[1])
    assert abs(up.std() * np.sqrt(256) - 1) < 0.02 and abs(up.mean()) < 1e-3
    scale = np.asarray(a["layers"]["ln1"]["scale"])
    assert abs(scale.mean() - 1) < 0.02 and abs(scale.std() - 0.1) < 0.01
    emb = np.asarray(a["embed"]["table"])
    np.testing.assert_array_equal(emb, np.asarray(weights.single_leaf(
        2 ** 35 + 1, "embed/table", (64, 8), "float32")))
    assert abs(emb.std() - 0.02) < 0.003


# ---------------------------------------------------------- metric arithmetic

def record(requests, window=10.0, **kw):
    base = {"window_s": window, "requests": requests, "steps": [],
            "setup_s": 1.5}
    base.update(kw)
    return base


def req(due, stamps, prompt=100, submit=None, admitted=None, failed=""):
    return {"due": due, "submit": due if submit is None else submit,
            "admitted": admitted, "stamps": stamps, "prompt_len": prompt,
            "failed": failed}


def test_ttft_from_due_time_with_a_stall_at_the_end():
    reqs = [req(0.0, [0.5, 0.6]), req(1.0, [1.2], submit=1.1),
            req(2.0, [2.4]), req(9.0, []),        # stalled: counts at 10.0
            req(-3.0, [4.0])]                     # of the lead-in: left out
    rec = record(reqs)
    vals = sorted([500.0, 200.0, 400.0, 1000.0])
    assert readers.ttft_ms(rec, 90) == pytest.approx(vals[3])
    assert readers.ttft_ms(rec, 50) == pytest.approx(vals[1])
    # a first token after the close also counts at the close
    rec = record([req(8.0, [12.0])])
    assert readers.ttft_ms(rec, 90) == pytest.approx(2000.0)


def test_itl_from_step_stamps():
    rec = record([req(0.0, [1.0, 1.0, 1.25, 1.75]),
                  req(0.0, [2.0, 2.5, 11.0]),    # last token after the close
                  req(-2.0, [-1.0, 0.5])])       # first token before the open
    # gaps: 0, 250, 500 and 500; the 11.0 and -1.0 tokens are outside
    assert readers.itl_ms(rec, 50) == pytest.approx(250.0)
    assert readers.itl_ms(rec, 100) == pytest.approx(500.0)


def test_serve_tokens_at_the_window_edges():
    rec = record([req(-1.0, [-0.5, 0.5, 1.0], prompt=100),   # first before
                  req(9.0, [9.5, 10.0, 10.5], prompt=40),    # 2 in window
                  req(9.9, [], prompt=70)])                  # never served
    # outputs 2 + 2; prompts: only the second's first token is in window
    assert readers.serve_tokens_per_s(rec) == pytest.approx((4 + 40) / 10)


def test_probe_reconstructs_passes_and_stamps_tokens():
    """Lanes per pass from the positions each slot advanced (from its
    first written position where admission mapped shared pages); output
    tokens are stamped by count."""
    from types import SimpleNamespace as NS

    from bench.drivers.serve import Probe, Rec
    a = NS(id=1, prompt=[0] * 5, output=[])
    b = NS(id=2, prompt=[0] * 3, output=[7, 8])
    slot = lambda r, pos, ws, gen: NS(request=r, pos=pos, write_start=ws,
                                      num_generated=gen)
    engine = NS(_active=[], finished=[])
    probe = Probe(engine)
    recs = {1: Rec(spec=None, due=0.0), 2: Rec(spec=None, due=0.0)}
    # a maps positions 0-1 from shared pages and runs 2-3; b runs 0-1
    engine._active = [slot(a, 4, 3, 0), slot(b, 2, 0, 0)]
    lanes = probe.observe(recs, 1.0)
    assert lanes == [[(3, False), (1, False)], [(4, False), (2, False)]]
    # a runs 4-6 (its last prompt position 4 makes its first token); b
    # runs 2-3 and finishes with two tokens
    engine._active = [slot(a, 7, 3, 3)]
    engine.finished = [b]
    lanes = probe.observe(recs, 2.0)
    assert lanes == [[(5, True), (3, True)], [(6, True), (4, True)],
                     [(7, True)]]
    assert recs[1].stamps == [2.0] * 3 and recs[2].stamps == [2.0] * 2
    assert recs[2].finished == 2.0 and recs[1].admitted == 1.0


def test_counter_and_host_time_metrics():
    c0 = {"engine": {"passes": 10, "lane_tokens": 50},
          "pool": {"shared_tokens_reused": 100}}
    c1 = {"engine": {"passes": 30, "lane_tokens": 190},
          "pool": {"shared_tokens_reused": 292}}
    reqs = [req(0.0, [1.0], prompt=256, admitted=0.5),
            req(1.0, [2.0], prompt=128, admitted=1.5),
            req(9.0, [], prompt=300, admitted=None)]
    rec = record(reqs, counters0=c0, counters1=c1,
                 steps=[(0.0, 0.1, 5, 5, 0.02), (0.2, 0.3, 5, 5, 0.03)])
    assert readers.lanes_per_pass(rec) == pytest.approx(7.0)
    assert readers.prefix_reuse_share(rec) == pytest.approx(
        100 * 192 / 384)
    assert readers.pass_ms(rec) == pytest.approx(20.0)
    assert readers.serve_step_mfu(rec) == pytest.approx(25.0)
    assert readers.gen_late_p99_ms(rec) == 0.0
    assert readers.idle_share(rec) is None          # no trace: nothing read


def test_traced_stretch_left_out_of_host_metrics():
    """The profiler slows the device while it records: steps, counters and
    admissions inside the traced stretch do not count."""
    c = lambda p, l, r: {"engine": {"passes": p, "lane_tokens": l},
                         "pool": {"shared_tokens_reused": r}}
    reqs = [req(0.0, [1.0], prompt=100, admitted=0.5, submit=0.2),
            req(4.0, [6.0], prompt=300, admitted=4.5, submit=4.9),
            req(7.0, [8.0], prompt=100, admitted=7.5, submit=7.0)]
    rec = record(reqs, counters0=c(0, 0, 0), counters1=c(30, 200, 150),
                 counters_traced=[c(10, 60, 50), c(20, 80, 100)],
                 traced=[4.0, 6.0],
                 steps=[(0.0, 1.0, 10, 10, 0.2), (4.0, 6.0, 10, 10, 0.2),
                        (7.0, 8.0, 10, 10, 0.3)])
    assert readers.pass_ms(rec) == pytest.approx(100.0)
    assert readers.serve_step_mfu(rec) == pytest.approx(25.0)
    assert readers.lanes_per_pass(rec) == pytest.approx(180 / 20)
    assert readers.prefix_reuse_share(rec) == pytest.approx(50.0)
    assert readers.gen_late_p99_ms(rec) == pytest.approx(200.0)


def test_percentile_is_nearest_rank():
    assert stats.percentile(list(range(1, 101)), 90) == 90
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.percentile([], 50) is None
