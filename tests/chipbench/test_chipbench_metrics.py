"""Metric arithmetic against values worked out by hand, and every
per-layer reading of the traces recorded on the chip."""
import types

import pytest

from chipbench_helpers import TINY_CONFIG, recorded, tiny_cell

from harness import peaks, reading, serve_loop, spec, trace_reduce

Req, Step = serve_loop.Req, serve_loop.Step
DENSE = spec.architecture(TINY_CONFIG)


def dense(layers, d_model, heads, kv_heads, head_dim, d_ff, vocab):
    """A dense GQA configuration (HF keys) and its cell as a reader sees
    it."""
    cfg = dict(TINY_CONFIG, num_hidden_layers=layers, hidden_size=d_model,
               num_attention_heads=heads, num_key_value_heads=kv_heads,
               head_dim=head_dim, intermediate_size=d_ff, vocab_size=vocab)
    return cfg, types.SimpleNamespace(arch=DENSE, config=cfg)


def read(name, rec):
    v = spec.metric_reader(name)(rec)
    return v[0] if isinstance(v, tuple) else v


def record(requests, steps=(), trace=None, traced=(), chips=1):
    rec = serve_loop.Record(
        cell=tiny_cell(), seed=0, chips=chips, t_start=10.0, t_end=20.0,
        setup_s=3.5, requests=list(requests), steps=list(steps),
        counters_start={}, counters_end={}, compiles_in_window=0,
        trace=trace, traced_steps=traced)
    rec.peaks = peaks.Peaks(bf16_flops=100.0, hbm_bytes_s=10.0,
                            source="test")
    return rec


def _req(due, times, admit_step=-1, i=0):
    r = Req(i, due, 16, len(times))
    r.times, r.admit_step = list(times), admit_step
    return r


@pytest.fixture
def rec():
    steps = [Step(11.0, 11.5, [16], []), Step(19.5, 20.5, [16], [])]
    reqs = [
        _req(9.0, [9.5, 10.5, 11.0], 0, 0),     # due before the window
        _req(11.0, [12.0, 12.5, 13.5], 0, 1),   # ttft 1.0
        _req(12.0, [15.0, 21.0], 1, 2),         # ttft 3.0, last gap ends late
        _req(18.0, [], -1, 3),                  # no token: ttft 20 - 18 = 2
    ]
    return record(reqs, steps)


def test_output_tokens_over_the_window(rec):
    # tokens at 10.5, 11.0, 12.0, 12.5, 13.5, 15.0 -> 6 in 10 s
    assert read("output_tok_s", rec) == pytest.approx(0.6)


def test_ttft_p95_over_every_due_request(rec):
    # due in window: ttft 1.0, 3.0, 2.0 -> p95 linear = 2 + 0.9 * 1
    assert read("ttft_p95_ms", rec) == pytest.approx(2900.0)


def test_itl_over_gaps_ending_in_the_window(rec):
    # gaps ending inside: 1.0 (at 10.5), 0.5 (11.0), 0.5 (12.5), 1.0
    # (13.5); 15.0 -> 21.0 ends after the window
    assert read("itl_p50_ms", rec) == pytest.approx(750.0)


def test_queue_wait_to_the_admitting_step(rec):
    # 11.0 - 11.0 = 0, 19.5 - 12.0 = 7.5, not admitted 20 - 18 = 2
    assert read("queue_wait_p95_ms", rec) == pytest.approx(
        1e3 * (2.0 + 0.9 * 5.5))


def test_setup_is_the_recorded_seconds(rec):
    assert read("setup_s", rec) == 3.5


def _trace():
    """One decode program run [0, 4) holding an attention kernel [0, 2)
    and an MLP kernel [2, 3); one prefill [5, 6) with an MLP kernel
    [5, 5.5); steps span [0, 4.5) and [4.5, 8)."""
    Op = trace_reduce.Op
    ops = [
        Op(0, 0.0, 4.0, 1.0, "while.1", "", "jit_decode_step_paged", ""),
        Op(0, 0.0, 2.0, 2.0, "fused_attention_partial.1", "",
           "jit_decode_step_paged", "jit(fused_attention_paged)/x"),
        Op(0, 2.0, 3.0, 1.0, "fused_mlp_chain.1", "",
           "jit_decode_step_paged", "jit(fused_mlp_chain)/y"),
        Op(0, 5.0, 6.0, 0.5, "fusion.2", "", "jit_prefill_paged", ""),
        Op(0, 5.0, 5.5, 0.5, "fused_mlp_chain.2", "",
           "jit_prefill_paged", "jit(fused_mlp_chain)/y"),
    ]
    mods = [(0, 0.0, 4.0, "jit_decode_step_paged"),
            (0, 5.0, 6.0, "jit_prefill_paged")]
    spans = [trace_reduce.Span(0.0, 4.5, "bench.step"),
             trace_reduce.Span(4.5, 8.0, "bench.step")]
    return trace_reduce.Reduced([0], mods, ops, spans, (0.0, 8.0))


def test_device_metrics_from_a_trace():
    cfg, cell = dense(layers=2, d_model=8, heads=2, kv_heads=1,
                      head_dim=4, d_ff=16, vocab=32)
    steps = [Step(0.0, 4.5, [], [3, 5]), Step(4.5, 8.0, [10], [])]
    rec = record([], steps, _trace(), (0, 2))
    rec.cell = cell
    # busy 4 + 1 of 8 s
    assert read("device_idle_share", rec) == pytest.approx(37.5)
    # idle inside steps: 0.5 + 2.5 over 2 steps
    assert read("sched_idle_ms_per_step", rec) == pytest.approx(1500.0)
    assert read("decode_step_ms", rec) == pytest.approx(4000.0)
    assert read("prefill_ms_per_ktok", rec) == pytest.approx(1e6 * 1.0 / 10)
    # attention: ctx 3 and 5 -> 8 positions, 2 layers
    flops = 4 * 8 * 2 * 4 * 2
    nbytes = (2 * 8 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2) * 2
    want = 100 * max(flops / 100.0, nbytes / 10.0) / 2.0
    assert read("paged_attn_roofline", rec) == pytest.approx(want)
    # mlp: decode rows 2, prefill rows 10, over 1.5 s of kernel time
    f1, b1 = DENSE.mlp_chain(cfg, 2)
    f2, b2 = DENSE.mlp_chain(cfg, 10)
    want = 100 * max((f1 + f2) / 100.0, (b1 + b2) / 10.0) / 1.5
    assert read("mlp_chain_roofline", rec) == pytest.approx(want)
    flops = (DENSE.decode_model_flops(cfg, [3, 5])
             + DENSE.prefill_model_flops(cfg, 10))
    assert read("step_mfu", rec) == pytest.approx(100 * flops / (5.0 * 100))


def test_costs_by_hand():
    cfg, _ = dense(layers=1, d_model=4, heads=2, kv_heads=1, head_dim=2,
                   d_ff=8, vocab=10)
    s = DENSE.shape_of(cfg)
    assert s.layer_matmul_params == 4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8
    assert DENSE.paged_attention(cfg, [1, 3]) == (4 * 4 * 2 * 2,
                                                  (2 * 4 * 2 * 2)
                                                  + 2 * 2 * 4 * 2)
    assert DENSE.prefill_model_flops(cfg, 2) == (
        2 * 2 * s.layer_matmul_params + 4 * 3 * 2 * 2 + 2 * 4 * 10)


def test_a_module_without_a_kernels_costs_raises_naming_it():
    """A reader that has kernel time to charge and no cost function in
    the cell's architecture module raises, naming the module and the
    metric; it never reads 0."""
    cfg, _ = dense(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                   d_ff=16, vocab=32)
    bare = types.SimpleNamespace(
        __file__="harness/arch/bare.py",
        decode_model_flops=DENSE.decode_model_flops,
        prefill_model_flops=DENSE.prefill_model_flops)
    steps = [Step(0.0, 4.5, [], [3, 5]), Step(4.5, 8.0, [10], [])]
    rec = record([], steps, _trace(), (0, 2))
    rec.cell = types.SimpleNamespace(arch=bare, config=cfg)
    for metric in ("mlp_chain_roofline", "paged_attn_roofline"):
        with pytest.raises(NotImplementedError,
                           match=f"{metric}: .*bare.py"):
            read(metric, rec)
    assert read("step_mfu", rec) > 0


def test_roofline_names_its_bound_and_refuses_zero_time():
    pk = peaks.Peaks(100.0, 10.0, "t")
    assert peaks.roofline_share(100.0, 1.0, 2.0, pk) == (50.0, "compute")
    assert peaks.roofline_share(1.0, 10.0, 4.0, pk) == (25.0, "memory")
    with pytest.raises(ValueError):
        peaks.roofline_share(1.0, 1.0, 0.0, pk)
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
    assert peaks.for_kind("TPU v5 lite").hbm_bytes_s == 819e9


def test_union_and_gaps():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]
    assert trace_reduce.union_length(iv) == 4.0
    assert trace_reduce.union_length(iv, 1.5, 5.5) == 2.0
    assert trace_reduce.gaps(iv, 0.0, 8.0) == [(3.0, 5.0), (6.0, 8.0)]
    # nested: a parent [0, 4) with children [0, 2) and [2, 3)
    assert trace_reduce._self_times([(0, 4), (0, 2), (2, 3)]) == [1, 2, 1]


def test_rooflines_charge_each_chip_its_share():
    """The same trace read as a four-chip cell's: each chip holds a
    quarter of the heads and of d_ff, so each kernel's needed work per
    chip, and its share of the roofline, is a quarter of one chip's."""
    _, cell = dense(layers=2, d_model=8, heads=4, kv_heads=4, head_dim=4,
                    d_ff=16, vocab=32)
    steps = [Step(0.0, 4.5, [], [3, 5]), Step(4.5, 8.0, [10], [])]
    one = record([], steps, _trace(), (0, 2), chips=1)
    four = record([], steps, _trace(), (0, 2), chips=4)
    for rec in (one, four):
        rec.cell = cell
    # attention: 8 positions, 4 q-heads of 4, 2 layers, over 2 s
    flops = 4 * 8 * 4 * 4 * 2
    nbytes = (2 * 8 * 4 * 4 * 2 + 2 * 2 * 4 * 4 * 2) * 2
    want = 100 * max(flops / 100.0, nbytes / 10.0) / 2.0
    assert read("paged_attn_roofline", one) == pytest.approx(want)
    assert read("paged_attn_roofline", four) == pytest.approx(want / 4)
    for name in ("mlp_chain_roofline", "paged_attn_roofline"):
        assert read(name, four) == pytest.approx(read(name, one) / 4)
    # the whole step's share already counts every chip's peak
    assert read("step_mfu", four) == pytest.approx(read("step_mfu", one) / 4)


def _mesh_trace():
    """Two chips, one decode program [0, 10) each, a layer scan's
    ``while`` enclosing it.  Chip 0: a sync all-reduce [2, 3) alone, an
    async pair whose start [4, 4.2) is followed by a fusion [4.2, 6)
    while the transfer is in flight and whose done [6, 7) waits alone;
    chip 1: an all-gather [2, 4) half covered by a fusion [3, 5).  A
    prefill's all-reduce is not the decode's."""
    Op = trace_reduce.Op
    dec = reading.DECODE
    ops = []
    for dev in (0, 1):
        ops.append(Op(dev, 0.0, 10.0, 0.0, "while.1", "", dec, ""))
    ops += [
        Op(0, 2.0, 3.0, 1.0, "all-reduce.7", "", dec, ""),
        Op(0, 4.0, 4.2, 0.2, "all-reduce-start.2", "", dec, ""),
        Op(0, 4.2, 6.0, 1.8, "fusion.3", "", dec, ""),
        Op(0, 6.0, 7.0, 1.0, "all-reduce-done.2", "", dec, ""),
        Op(1, 2.0, 4.0, 2.0, "ag.5",
           "%ag.5 = bf16[8]{0} all-gather(%x), dimensions={0}", dec, ""),
        Op(1, 3.0, 5.0, 2.0, "fusion.9", "", dec, ""),
        Op(0, 12.0, 13.0, 1.0, "all-reduce.1", "", reading.PREFILL, ""),
    ]
    mods = [(0, 0.0, 10.0, dec), (1, 0.0, 10.0, dec),
            (0, 12.0, 13.0, reading.PREFILL)]
    spans = [trace_reduce.Span(0.0, 14.0, "bench.step")]
    return trace_reduce.Reduced([0, 1], mods, ops, spans, (0.0, 14.0))


def test_collective_kinds_by_name_or_opcode():
    Op = trace_reduce.Op
    kinds = [trace_reduce.collective_kind(Op(0, 0, 1, 1, n, t, "", ""))
             for n, t in [("all-reduce.7", ""),
                          ("all-reduce-start.2", ""),
                          ("collective-permute-done.1", ""),
                          ("reduce-scatter.4", ""),
                          ("x.1", "%x.1 = f32[2]{0} all-to-all(%y)"),
                          ("fusion.3", "%fusion.3 = f32[2]{0} fusion(%a)"),
                          ("all-reduce-fusion.1", "")]]
    assert kinds == ["all-reduce", "all-reduce-start",
                     "collective-permute-done", "reduce-scatter",
                     "all-to-all", None, None]


def test_exposed_collectives_per_decode_step():
    red = _mesh_trace()
    # chip 0: 1 + 0.2 + 1 (the fusion runs between start and done);
    # chip 1: 2 - 1 (half under the fusion)
    assert trace_reduce.exposed_collective_s(red, 0, reading.DECODE) == (
        pytest.approx(2.2), 3)
    assert trace_reduce.exposed_collective_s(red, 1, reading.DECODE) == (
        pytest.approx(1.0), 1)
    rec = record([], (), red, (0, 1), chips=2)
    # (2.2 + 1) s over 2 chips, one decode step each
    assert read("collective_exposed_ms_per_step", rec) == pytest.approx(
        1600.0)
    # one chip's trace, no collective: nothing to read
    assert read("collective_exposed_ms_per_step", record([], (), _trace(),
                                                          (0, 2))) is None


# Every per-layer reading of the recorded traces (``recorded``) as the
# harness read them before the architecture modules, by repr.
RECORDED = {
    ("decode", "qwen3-8b-4L.chat"): {
        "sched_idle_ms_per_step": "3.5506849999999908",
        "queue_wait_p95_ms": "10.000000000000002",
        "decode_step_ms": "11.236072666666667",
        "prefill_ms_per_ktok": "174.77439062500005",
        "paged_attn_roofline": "(1.1063890591736816, 'memory-bound')",
        "mlp_chain_roofline": "(46.05555594990408, 'memory-bound')",
        "device_idle_share": "19.175388568056107",
        "step_mfu": "1.2272928019104579",
    },
    ("decode", "mistral-nemo-12b-4L.reason"): {
        "sched_idle_ms_per_step": "3.5506849999999908",
        "decode_step_ms": "11.236072666666667",
        "paged_attn_roofline": "(1.1063890591736816, 'memory-bound')",
        "mlp_chain_roofline": "(67.15564101286661, 'memory-bound')",
        "device_idle_share": "19.175388568056107",
        "step_mfu": "1.7146797228181587",
    },
    ("decode_spans", "qwen3-8b-4L.chat"): {
        "sched_idle_ms_per_step": "3.7358136666666684",
        "queue_wait_p95_ms": "10.000000000000002",
        "decode_step_ms": "11.233570666666667",
        "prefill_ms_per_ktok": "174.82464062499994",
        "paged_attn_roofline": "(1.105829891451103, 'memory-bound')",
        "mlp_chain_roofline": "(46.05450709828453, 'memory-bound')",
        "device_idle_share": "19.97986480138304",
        "step_mfu": "1.2274100918649105",
    },
    ("decode_spans", "mistral-nemo-12b-4L.reason"): {
        "sched_idle_ms_per_step": "3.7358136666666684",
        "decode_step_ms": "11.233570666666667",
        "paged_attn_roofline": "(1.105829891451103, 'memory-bound')",
        "mlp_chain_roofline": "(67.15411163597852, 'memory-bound')",
        "device_idle_share": "19.97986480138304",
        "step_mfu": "1.714843591380149",
    },
}


@pytest.mark.parametrize("trace,cell", sorted(RECORDED),
                         ids=lambda v: v.replace(".", "_"))
def test_every_reading_of_the_recorded_traces_is_as_before(trace, cell):
    c = spec.load_cell(cell)
    rec = recorded(trace, c)
    got = {m["name"]: repr(spec.metric_reader(m["name"])(rec))
           for m in c.per_layer}
    assert got == RECORDED[(trace, cell)]
