"""The benchmark's yardstick: work functions against hand-reckoned counts,
and the trace reducer's interval arithmetic, collectives and breakdown on
hand-made events."""
import json
import pathlib

import pytest

from benchmarks.chip import harness, inputs, peaks, trace, work

CONFIGS = harness.HERE / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_parameter_and_cache_counts():
    l4, full = _cfg("qwen3-1.7b-L4"), _cfg("qwen3-1.7b")
    # 151,936 x 2,048 tied embedding = 311,164,928; a layer: q,k,v,o
    # 2048 x (2048 + 1024 + 1024) + 2048 x 2048 = 12,582,912, MLP
    # 3 x 2048 x 6144 = 37,748,736, two norms 4,096: 50,335,744.
    assert work.n_params(l4) == 311_164_928 + 4 * 50_335_744 == 512_507_904
    assert work.n_params(full) == 311_164_928 + 28 * 50_335_744 == 1_720_565_760
    # key and value, 8 heads x 128, bf16, 28 layers
    assert work.kv_bytes_per_token(full) == 28 * 2 * 1024 * 2 == 114_688
    assert work.weight_bytes(full) == 2 * 1_720_565_760


@pytest.mark.parametrize("traffic", sorted(p.stem for p in (harness.HERE / "traffic").glob("azure-*.json")))
def test_serving_rounds_give_every_seed_the_same_sizes(traffic):
    tf = json.loads((harness.HERE / "traffic" / f"{traffic}.json").read_text())
    a, b = inputs.serve_rounds(tf, 7), inputs.serve_rounds(tf, 3000000019)
    assert [p for p, _ in a] == [p for p, _ in b] == [512, 1024, 1536]
    for (_, x), (_, y) in zip(a, b):
        assert sorted(x) == sorted(y) and list(x) != list(y) and len(x) == tf["batch"]
        assert 1 <= min(x) and max(x) == tf["output_len"]["max"]
    # the longest prompt and its longest answer fit the reserve
    assert 1536 + tf["output_len"]["max"] <= tf["max_len"]
    # stratified quantiles keep the distribution's median
    assert sorted(inputs.lognormal_quantiles(129, 1.0, 3))[1] == pytest.approx(129)


def test_train_step_flops():
    l4 = _cfg("qwen3-1.7b-L4")
    matmul = 4 * (50_335_744 - 4_096) + 311_164_928
    attn = 4 * 2 * 2 * 2048 * (4 * 1024 * 1025 // 2)
    assert work.train_step_flops(l4, 4, 1024) == 3 * (2 * matmul * 4096 + attn)
    assert work.train_flops_per_token(l4, 1024) == pytest.approx(3.125e9, rel=1e-3)
    assert work.train_step_flops(l4, 4, 1024) == pytest.approx(12.80e12, rel=1e-3)


def test_decode_step_counts_only_the_live_cache():
    full = _cfg("qwen3-1.7b")
    flops, nbytes = work.decode_step_work(full, [1600] * 8)
    assert nbytes == 2 * 1_720_565_760 + 8 * 1600 * 114_688
    assert work.decode_step_work(full, [1000] * 8)[1] < nbytes   # capacity plays no part
    t, bound = work.least_time_s(flops, nbytes, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_kernel_work():
    assert work.flash_forward_work(1, 4, 1, 1, 2) == (4 * 2 * 10, 2 * 4 * 2 * 4)
    assert work.flash_backward_work(1, 4, 1, 1, 2)[0] == 2 * work.flash_forward_work(1, 4, 1, 1, 2)[0]
    assert work.decode_attention_work([3, 5], 2, 1, 4) == (4 * 2 * 4 * 8, 2 * (2 * 4 * 8 + 2 * 2 * 2 * 4))
    assert work.rmsnorm_work(3, 8) == (96, 2 * 2 * 24 + 32)


def test_interval_arithmetic():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)] and trace.total(u) == 7
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert trace.subtract([(0, 3), (5, 9)], [(2, 6)]) == [(0, 2), (6, 9)]


def _op(name, code, shape="f32[8]"):
    return f"%{name} = {shape} {code}(f32[8] %p), calls=%c"


def test_summary_busy_collectives_and_breakdown():
    ops = [(_op("while.1", "while"), 0.0, 6.0),          # holds the two below
           (_op("fusion.1", "fusion"), 0.0, 2.0),
           (_op("all-reduce.3", "all-reduce"), 2.0, 3.0),
           (_op("fusion.2", "fusion"), 7.0, 8.0)]
    async_ops = [(_op("all-gather-start.1", "all-gather-start"), 2.5, 4.0),
                 (_op("copy-start.1", "copy-start"), 0.0, 9.0)]
    spans = [("bench.step", 0.0, 8.5), ("$trainer.py:139 train", 6.0, 7.0),
             ("bench.decode", 9.2, 9.5)]
    s = trace.Summary([ops], [async_ops], [[("jit_a(1)", 0.0, 6.0), ("jit_a(1)", 7.0, 8.0),
                                            ("jit_b(2)", 8.5, 9.0)]], spans, (0.0, 10.0))
    assert s.busy_s == 7.0 and s.idle_share() == pytest.approx(0.3)
    # collectives: [2, 4]; the compute the while loop holds covers [0, 2]
    assert s.collective(0) == (2.0, 2.0)
    assert s.module_runs(r"^jit_a\b") == [6.0, 1.0]
    assert s.heaviest_module_runs() == [6.0, 1.0]
    b = s.breakdown()
    assert [n for n, _ in b["device_ops"]][0].startswith("fusion.1 ")
    assert all(not n.startswith("while") for n, _ in b["device_ops"])
    assert b["idle_gaps"][0] == ["bench.window", 2.0]                  # [8, 10]
    assert b["idle_gaps"][1] == ["bench.step / $trainer.py:139 train", 1.0]   # [6, 7]


def test_peaks_cite_their_source():
    for kind, p in peaks.PEAKS.items():
        assert p["source"] and p["bf16_flops"] > 0 and p["hbm_bytes_per_s"] > 0, kind
    assert pathlib.Path(peaks.__file__).read_text().count("TPU v5e") >= 1


FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def test_reducer_on_a_trace_recorded_on_one_chip():
    """A TPU v5e trace (kept in fixtures/): five runs of a jitted 2048^2
    bf16 matmul, each under ``bench.work``, with 10 ms host sleeps under
    ``bench.sleep`` between them, all inside ``bench.window``."""
    s = trace.Summary.from_file(str(FIXTURES / "fixture_1chip.xplane.pb"), 1)
    runs = s.heaviest_module_runs()
    assert len(runs) == 5 and all(r > 0 for r in runs)
    assert 0 < s.busy_s < s.window_s and s.window_s >= 0.05
    assert s.idle_share() > 0.5                      # the sleeps leave the chip idle
    gaps = s.breakdown()["idle_gaps"]
    assert gaps[0][0].startswith("bench.sleep") and gaps[0][1] >= 0.009
    ops = s.breakdown()["device_ops"]
    assert ops and sum(t for _, t in ops) <= s.busy_s * 1.0001
    assert s.collective(0) == (0, 0)

