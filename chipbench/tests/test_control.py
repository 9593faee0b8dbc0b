"""The controls: the plain reference computed in the precision below the
configuration's, put in the program's place, must come out as not correct
against the limits the configurations and the mixes state.  Sizes a CPU test run can hold;
the same controls were read on the chip at the cells' own sizes (PERF.md)."""
import json
import os

import numpy as np

from chipbench.reference import resnet_v1, transformer_lm
from chipbench.runners import train

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_fp8_training_control_fails_the_limits():
    config = _config("resnet50_v1")
    # the cell's depth at an eighth of its widths: rounding compounds with
    # depth, and four blocks of fp8 stay inside limits set for fifty layers
    sizes = {"layers": [3, 4, 6, 3], "channels": [8, 16, 32, 64, 128],
             "classes": 100, "hw": 64, "batch": 16}
    batches = resnet_v1.make_batches(5, sizes, 3)
    opt = config["optimizer"]
    ref = resnet_v1.follow(5, sizes, batches, opt, 3, "float32")
    again = resnet_v1.follow(5, sizes, batches, opt, 3, "float32")
    rows, ok = train.judge(train.compare(again, ref), config["limits"])
    assert ok, rows                      # the reference agrees with itself
    control = resnet_v1.follow(5, sizes, batches, opt, 3, "fp8")
    rows, ok = train.judge(train.compare(control, ref), config["limits"])
    assert not ok, rows


def test_a_step_that_changes_nothing_and_a_batch_part_left_out_fail():
    config = _config("resnet50_v1")
    sizes = {"layers": [1, 1, 1, 1], "channels": [8, 16, 32, 64, 128],
             "classes": 10, "hw": 32, "batch": 16}
    batches = resnet_v1.make_batches(9, sizes, 3)
    opt = config["optimizer"]
    ref = resnet_v1.follow(9, sizes, batches, opt, 3, "float32")
    frozen = dict(ref, dw_norms=np.zeros_like(ref["dw_norms"]))
    rows, ok = train.judge(train.compare(frozen, ref), config["limits"])
    assert not ok and not dict((r[0], r[3]) for r in rows)["dw_norm_gap"]
    half = [(x[:8], y[:8]) for x, y in batches]
    part = resnet_v1.follow(9, dict(sizes, batch=8), half, opt, 3, "float32")
    numbers = train.compare(part, ref)
    assert numbers["loss_rel_gap"] > config["limits"]["loss_rel_gap"]


def _serving_limits():
    """Every serving mix's own limit, by the mix's name."""
    out = {}
    for name in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        with open(os.path.join(BENCH, "traffic", name)) as f:
            mix = json.load(f)
        if mix["kind"] == "serve":
            out[name[:-len(".json")]] = mix["limits"]["served_gap_per_1k"]
    return out


LIMITS = _serving_limits()


def test_bfloat16_serving_control_fails_every_mixes_limit():
    sizes = _config("gpt2_small")["sizes"]   # the cell's own widths and depth
    rng = np.random.RandomState(3)
    # the control's number needs no decoding: at each position of the same
    # prompts and tokens, the gap of the token the lower precision puts first
    requests = [(rng.randint(0, sizes["vocab_size"], 16).tolist(),
                 rng.randint(0, sizes["vocab_size"], 176).tolist())
                for _ in range(2)]
    got = transformer_lm.served_gaps(3, sizes, requests, 256, "bfloat16")
    assert got["control_not_best"] > 0
    assert len(LIMITS) >= 2
    for mix, limit in LIMITS.items():
        assert got["control_gap_per_1k"] > limit, (mix, got)


def test_the_references_own_greedy_tokens_have_no_gap_and_altered_ones_do():
    import jax.numpy as jnp

    config = _config("gpt2_small")
    sizes = dict(config["sizes"], vocab_size=4096, num_layers=2,
                 max_length=64)
    w = transformer_lm.make_weights(4, sizes)
    seq = np.random.RandomState(4).randint(0, 4096, 24).tolist()
    for _ in range(12):
        tokens = np.zeros(64, np.int32)
        tokens[:len(seq)] = seq
        logits = transformer_lm.forward(w, jnp.asarray(tokens), sizes)
        seq.append(int(jnp.argmax(logits[len(seq) - 1])))
    sound = transformer_lm.served_gaps(4, sizes, [(seq[:24], seq[24:])], 64)
    assert sound["served_gap_per_1k"] == 0.0 and sound["not_best"] == 0
    altered = [(t + 1) % 4096 for t in seq[24:]]
    broken = transformer_lm.served_gaps(4, sizes, [(seq[:24], altered)], 64)
    assert broken["served_gap_per_1k"] > max(LIMITS.values())
