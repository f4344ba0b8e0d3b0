"""Operation and byte counts against counts made by hand."""
import common
import flops


def _cfg(name):
    return common.load_json(f"{common.BENCH_DIR}/configs/{name}.json")


def test_chatglm_weights_and_cache_by_hand():
    c = _cfg("chatglm3-6b")
    # per layer: q 4096x4096, k and v 4096x256, o 4096x4096, three
    # 4096x13696 feed-forward matrices, two rms weights
    layer = (4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
             + 3 * 4096 * 13696 + 2 * 4096)
    assert layer == 203_956_224
    total = 28 * layer + 2 * 65024 * 4096 + 4096
    assert flops.param_counts(c)["total"] == total == 6_243_454_976
    assert flops.weight_bytes(c, 2) == 12_486_909_952  # 12.49 GB in bf16
    # 28 layers x (k, v) x 2 kv heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(c, 2) == 28_672


def test_bert_large_counts_by_hand():
    c = _cfg("bert-large")
    layer = 4 * 1024 * 1024 + 2 * 1024 * 4096 + 4 * 1024
    pc = flops.param_counts(c)
    assert pc["layers"] == 24 * layer == 302_088_192
    assert pc["total"] == (24 * layer + 2 * 30522 * 1024 + 512 * 1024
                           + 2 * 1024)
    # 3 x (2 x matmul weights of the 24 layers and the head + 4 x d x 512
    # attention keys per layer)
    per_tok = 3 * (24 * (2 * 12 * 1024 * 1024 + 4 * 1024 * 512)
                   + 2 * 1024 * 30522)
    assert flops.train_flops_per_token(c, 512) == per_tok
    assert 2.1e9 < per_tok < 2.2e9


def test_prefill_flops_count_causal_keys():
    c = _cfg("chatglm3-6b")
    one = flops.token_flops(c, 1)
    # two tokens from position 0: keys 1 and 2
    assert flops.prefill_flops(c, 0, 2) == one + flops.token_flops(c, 2)
    # a chunk split in two costs what the whole does
    assert flops.prefill_flops(c, 0, 128) == (
        flops.prefill_flops(c, 0, 64) + flops.prefill_flops(c, 64, 64))


def test_decode_call_is_bound_by_the_weight_read():
    c = _cfg("chatglm3-6b")
    peaks = common.peaks_for("TPU v5 lite")
    rows = [(500, 1, True)] * 16
    t = flops.serve_call_roofline_s(c, rows, peaks, 2, 2)
    w = (flops.param_counts(c)["total"] - 65024 * 4096) * 2
    assert t == (w + 16 * 501 * 28_672) / 819e9
    assert 0.0145 < t < 0.0150
