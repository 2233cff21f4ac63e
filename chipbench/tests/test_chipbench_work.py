"""FLOPs and bytes against hand arithmetic; traffic and weights are fixed by
the seed; the peaks table refuses unknown devices."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import chipbench_testkit  # noqa: E402,F401

from chipbench import core, traffic, weights  # noqa: E402

MAN = core.manifest()
QWEN = core.load_config(MAN, "qwen3-4b")
GQA_WORK = core.work("gqa")


def test_qwen3_4b_decode_and_prefill_by_hand():
    d, h, kv, hd, ff, n, v = 2560, 32, 8, 128, 9728, 36, 151936
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    assert GQA_WORK.layer_matmul_params(QWEN) == layer == 100_925_440
    weight_bytes = 2 * (n * (layer + 2 * d) + v * d + d)
    assert GQA_WORK.weight_bytes(QWEN) == weight_bytes == 8_044_917_760
    row = n * 2 * kv * hd * 2
    assert GQA_WORK.cache_bytes_per_token(QWEN) == row == 147_456
    lengths = [100, 300]
    flops, nbytes = GQA_WORK.decode(QWEN, lengths)
    assert flops == 2 * 2 * (n * layer + v * d) + 4 * n * h * hd * (101 + 301)
    assert nbytes == weight_bytes + row * 400 + row * 2 + 2 * 2 * 2 * d
    flops, nbytes = GQA_WORK.prefill(QWEN, 256)
    assert flops == 2 * 256 * n * layer + 4 * n * h * hd * 256 * 257 / 2 + 2 * v * d
    assert nbytes == weight_bytes + row * 256 + 2 * 256 * d


@pytest.mark.parametrize("cell,batch", [("qwen3-4b.chat", 16),
                                        ("qwen3-4b.decode-heavy", 8)])
def test_decode_batch_is_derived_from_the_cache_bytes(cell, batch):
    c = core.cell(MAN, cell)
    model = core.load_config(MAN, c["config"])
    assert core.decode_batch(model, core.load_mix(c["traffic"]),
                             core.work(model["work"])) == batch


def test_peaks_lookup_refuses_unknown_kinds():
    pk = core.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9 and "TPU v5e" in pk["source"]
    with pytest.raises(core.BenchError):
        core.peaks("cpu")
    assert core.least_time_s(197e12, 1.0, pk) == 1.0
    assert core.bound_of(1.0, 819e9, pk) == "memory"


def test_percentile_matches_numpy():
    xs = list(np.random.default_rng(3).lognormal(size=101))
    for q in (50, 95, 99):
        assert core.percentile(xs, q) == pytest.approx(np.percentile(xs, q), rel=1e-12)
    with pytest.raises(core.BenchError):
        core.percentile([], 95)


@pytest.mark.parametrize("mix_name", ["chat", "decode-heavy"])
def test_traffic_is_fixed_by_the_seed(mix_name):
    mix = core.load_mix(mix_name)
    a = traffic.make(mix, 2**31 + 5, 30.0, 1000)
    b = traffic.make(mix, 2**31 + 5, 30.0, 1000)
    c = traffic.make(mix, 7, 30.0, 1000)
    assert [(s.prompt.tolist(), s.max_new, s.due_s) for s in a] == \
        [(s.prompt.tolist(), s.max_new, s.due_s) for s in b]
    # another seed: the same lengths at the same times, other tokens
    assert [(len(s.prompt), s.max_new, s.due_s) for s in a] == \
        [(len(s.prompt), s.max_new, s.due_s) for s in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # another schedule seed: the same lengths in another order
    d = traffic.make(dict(mix, schedule_seed=mix["schedule_seed"] + 1), 7, 30.0, 1000)
    assert sorted(len(s.prompt) for s in d) == sorted(len(s.prompt) for s in c)
    assert [len(s.prompt) for s in d] != [len(s.prompt) for s in c]
    for s in a:
        assert mix["prompt"]["min"] <= len(s.prompt) <= mix["prompt"]["max"]
        assert len(s.prompt) + s.max_new <= mix["max_seq_len"]
        assert 0 <= s.due_s < 30.0
        assert s.prompt.min() >= 1


def test_stacked_weights_equal_the_layer_draws():
    import jax
    import jax.numpy as jnp

    lv = [weights.Leaf(("a",), (3, 5), 0.1), weights.Leaf(("b",), (4,), 0.5, stacked=False),
          weights.Leaf(("c",), (2,), 0.06, offset=1.0)]
    tree = weights.make_params(lv, 3, 2**40 + 9, "bfloat16")
    root = weights.root_key(2**40 + 9)
    for layer in range(3):
        w = weights.layer_weights(lv, root, layer, jnp.float32)
        assert np.array_equal(np.asarray(tree["a"][layer], np.float32), np.asarray(w[("a",)]))
        assert np.array_equal(np.asarray(tree["c"][layer], np.float32) + 1.0,
                              np.asarray(w[("c",)]))
    g = weights.global_weights(lv, root, jnp.float32)
    assert np.array_equal(np.asarray(tree["b"], np.float32), np.asarray(g[("b",)]))
    ints = np.asarray(tree["a"], np.float32) / 2.0 ** lv[0].log2_step
    assert np.array_equal(ints, np.round(ints)) and np.abs(ints).max() <= 127
    assert jax.tree.structure(tree) == jax.tree.structure({"a": 0, "b": 0, "c": 0})


@pytest.mark.parametrize("config", [c["name"] for c in MAN["configs"]])
def test_weights_match_the_program_tree_at_full_size(config):
    import jax

    from repro.configs import get_config
    from repro.models import abstract_params

    model = core.load_config(MAN, config)
    ref = core.reference(model["work"])
    lv = ref.leaves(model)
    got = jax.eval_shape(lambda: weights.make_params(
        lv, model["num_hidden_layers"], 1, model["torch_dtype"], wrap_stages=ref.wrap))
    want = abstract_params(get_config(model["arch"]))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)

