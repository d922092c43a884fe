"""``perf/flops.py`` against counts made by hand from the published sizes."""
from perf import flops
from perf.spec import PERF_DIR, load_json, load_module

GCLM = load_json(PERF_DIR / "configs" / "gc-lm-110m.json")["model"]
WHISPER = load_json(PERF_DIR / "configs" / "whisper-base-fp32.json")["model"]


def test_gclm_forward_flops_by_hand():
    # per layer: q,k,v,o 4*768*768 = 2,359,296; SwiGLU 3*768*3072 =
    # 7,077,888 -> 9,437,184 multiply-adds per token; 12 layers =
    # 113,246,208; tied logits 768*32000 = 24,576,000; 137,822,208 in all
    # (the 137,841,408 parameters less 25 norm vectors of 768).
    s = 1024
    matmul = 2 * s * 137_822_208
    attention = 12 * 2 * s * s * 768          # causal: half of 4*S^2*d
    assert flops.forward_flops(GCLM, s) == matmul + attention
    assert matmul + attention == 301_587_234_816


def test_whisper_forward_flops_by_hand():
    t, s, d = 1500, 448, 512
    # encoder layer: 2*T*(4*d^2 + 2*d*2048) + 4*T^2*d
    enc = 2 * t * (4 * d * d + 2 * d * 2048) + 4 * t * t * d
    assert enc == 14_045_184_000
    # decoder layer: self 2*S*(4d^2 + 2*d*2048) + causal 2*S^2*d,
    # cross q,o 2*S*2*d^2 + k,v of the source 2*T*2*d^2 + 4*S*T*d
    dec = (2 * s * (4 * d * d + 2 * d * 2048) + 2 * s * s * d
           + 2 * s * 2 * d * d + 2 * t * 2 * d * d + 4 * s * t * d)
    assert dec == 6_442_975_232
    logits = 2 * s * d * 51865
    total = 6 * enc + 6 * dec + logits
    assert flops.forward_flops(WHISPER, s) == total
    assert total == 146_722_127_872   # ~147 GFLOP per utterance forward


def test_step_flops_count_useful_and_redundant_work():
    traffic = {"rows_per_shard": 2, "seq_len": 448}
    one = 3 * 2 * flops.forward_flops(WHISPER, 448)
    got = flops.step_flops(WHISPER, traffic, k_shards=8, ranks=1)
    assert got == {"useful": one, "backward": 8 * one}
    got4 = flops.step_flops(GCLM, {"rows_per_shard": 1, "seq_len": 1024},
                            k_shards=4, ranks=4)
    assert got4["backward"] == 4 * got4["useful"]


def test_combine_bytes_read_the_stack_once_and_write_once():
    assert flops.combine_bytes(137_841_408, 8, 1) == 9 * 4 * 137_841_408
    assert flops.combine_bytes(100, 1, 4) == 4 * 2 * 4 * 100


def test_a_configuration_brings_its_own_forward_count():
    from types import SimpleNamespace

    own = SimpleNamespace(forward_flops=lambda m, s: 1000 * s + m["vocab"])
    traffic = {"rows_per_shard": 2, "seq_len": 16}
    got = flops.step_flops(GCLM, traffic, k_shards=4, ranks=1,
                           reference=own)
    shard = 3 * 2 * (1000 * 16 + 32000)
    assert got == {"useful": shard, "backward": 4 * shard}


def test_the_two_configurations_keep_the_dense_hand_counts():
    # neither reference module defines forward_flops: the formula prices
    # both, at the totals counted by hand above
    for name, model, seq, per_row in (
            ("gc-lm-110m", GCLM, 1024, 301_587_234_816),
            ("whisper-base-fp32", WHISPER, 448, 146_722_127_872)):
        ref = load_module(PERF_DIR / "configs" / f"{name}.py")
        assert not hasattr(ref, "forward_flops")
        got = flops.step_flops(model, {"rows_per_shard": 1, "seq_len": seq},
                               k_shards=1, ranks=1, reference=ref)
        assert got == {"useful": 3 * per_row, "backward": 3 * per_row}
