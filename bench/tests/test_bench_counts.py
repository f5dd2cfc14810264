"""The operation and byte counts against numbers worked by hand for one
layer of each configuration."""
import json

import pytest

from bench.families import dense_gqa_counts as C
from bench.tests.tiny import BENCH

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def one_layer(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["num_hidden_layers"] = 1
    return cfg


def test_nemo_layer_by_hand():
    cfg = one_layer("mistral-nemo-12b.tp4")
    # q 5120x8x128, k and v 5120x2x128, o 8x128x5120, gate/up/down 5120x14336
    assert C.layer_params(cfg) == 5_242_880 + 2 * 1_310_720 + 5_242_880 + 3 * 73_400_320
    assert C.layer_params(cfg) == 233_308_160
    head = 5120 * 32768
    assert C.matrix_params(cfg) == 233_308_160 + head
    # one row decoding at position 9 sees 10 keys
    attn = 4 * 8 * 128 * 10
    assert C.decode_flops(cfg, 1, 9) == 2 * 233_308_160 + attn + 2 * head
    kv = 2 * 10 * 2 * 128 * 2
    assert C.decode_bytes(cfg, 1, 9) == (233_308_160 + head) * 2 + kv + 5120 * 2 + 32768 * 4
    # a 4-token prompt: causal keys 1+2+3+4
    assert C.prefill_flops(cfg, 1, 4) == 2 * 4 * 233_308_160 + 4 * 8 * 128 * 10 + 2 * head
    t, which = C.least_seconds(C.decode_flops(cfg, 1, 9), C.decode_bytes(cfg, 1, 9), PEAK)
    assert which == "memory"
    assert t == pytest.approx(C.decode_bytes(cfg, 1, 9) / 819e9)


def test_starcoder2_layer_by_hand():
    cfg = one_layer("starcoder2-15b.tp4")
    # q 6144x12x128, k and v 6144x1x128, o 12x128x6144, up/down 6144x24576
    assert C.layer_params(cfg) == 9_437_184 + 2 * 786_432 + 9_437_184 + 2 * 150_994_944
    assert C.layer_params(cfg) == 322_437_120
    head = 6144 * 12288
    # 6144 prompt tokens against a 4096 window: 1+...+4096, then 2048 x 4096
    keys = 4096 * 4097 // 2 + 2048 * 4096
    assert keys == 16_779_264
    assert C.prefill_flops(cfg, 2, 6144) == (2 * 2 * 6144 * 322_437_120
                                             + 4 * 12 * 128 * 2 * keys + 2 * 2 * head)
    # the ring cache keeps 4096 of them
    assert C.prefill_bytes(cfg, 1, 6144) == ((322_437_120 + head) * 2 + 6144 * 6144 * 2
                                             + 2 * 4096 * 128 * 2 + 6144 * 4 + 12288 * 4)
    # past the window a decode step still reads 4096 keys
    assert C.keys_seen(6200, 4096) == 4096
    assert C.decode_flops(cfg, 3, 6200) == 3 * 2 * 322_437_120 + 4 * 12 * 128 * 3 * 4096 + 3 * 2 * head
    t, which = C.least_seconds(C.prefill_flops(cfg, 4, 6144), C.prefill_bytes(cfg, 4, 6144), PEAK)
    assert which == "compute"


def test_call_work_steps():
    cfg = one_layer("mistral-nemo-12b.tp4")
    pf, pb, steps = C.call_work(cfg, 3, 512, 128)
    assert len(steps) == 127
    assert steps[0] == (C.decode_flops(cfg, 3, 512), C.decode_bytes(cfg, 3, 512))
    assert steps[-1][0] == C.decode_flops(cfg, 3, 638)
