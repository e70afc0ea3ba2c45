"""The controls, at sizes a test run can hold: the reference computed in
the nearest precision below the one the configuration states, put in the
program's place, reads well above what the reference reads against
itself. (That each comes out as not correct through a run's own comparison
is test_benchmark_control_run.py's; their readings at the cells' own
sizes, on the chip, are in PERF.md.)"""
import numpy as np
import pytest


def test_decoder_control_in_lower_precision_picks_tokens_below_the_best():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import decoder as ref
    cfg = {"hidden_size": 128, "ffn_dim": 256, "num_hidden_layers": 4,
           "vocab_size": 2048, "init_std": 0.02}
    w = jax.jit(lambda k: ref.init_weights(k, cfg))(jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    seqs = []
    for _ in range(4):
        prompt = rng.integers(0, 2048, 24).tolist()
        toks = list(prompt)
        for _ in range(8):                  # the reference's own greedy tokens
            logits = ref.forward(w, jnp.asarray(toks, jnp.int32), 4)
            toks.append(int(logits[-1].argmax()))
        seqs.append((prompt, toks[24:]))
    read = {}
    for lowp in ("bfloat16", "float8_e4m3fn"):
        gaps = ref.served_gaps(w, 4, seqs, 64, lowp=lowp)
        assert max(float(g.max()) for g, _ in gaps) == 0.0   # its own best
        read[lowp] = max(float(c.max()) for _, c in gaps)
    # a widest gap over 32 positions: float8 always picks some token below
    # the best, bfloat16 need not at this size
    assert read["float8_e4m3fn"] > 0.0
    assert 0.0 <= read["bfloat16"] <= read["float8_e4m3fn"]


def test_decoder_weights_are_the_seeds_and_layer_by_layer_alike():
    import jax
    from benchmark.models import decoder
    cfg = {"hidden_size": 32, "ffn_dim": 64, "num_hidden_layers": 2,
           "vocab_size": 64, "init_std": 0.02}
    a = decoder.make_weights(2 ** 31 + 1, cfg)
    b = decoder.make_weights(2 ** 31 + 1, cfg)
    c = decoder.make_weights(2 ** 31 + 2, cfg)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["emb"] == c["emb"]).all())
    assert not bool((a["layers"][0]["wq"] == a["layers"][1]["wq"]).all())


@pytest.mark.parametrize("control", ["float8_e4m3fn", "half"])
def test_resnet_control_and_fault_read_above_the_reference(control):
    import jax
    import jax.numpy as jnp
    from benchmark import compare
    from benchmark.kinds import fit_ring
    from benchmark.reference import resnet50 as ref
    image, labels, rows = (64, 64, 3), 10, 16
    w = jax.jit(lambda k: ref.init_weights(k, image, labels, jnp.float32))(
        jax.random.PRNGKey(1))
    batches = fit_ring.make_ring(4, 3, rows, image, labels)
    base = ref.follow(w, batches, 1e-3)
    kw = {"rows": slice(0, rows // 2)} if control == "half" \
        else {"lowp": jnp.dtype(control)}
    gaps = compare.training_gaps(ref.follow(w, batches, 1e-3, **kw), base)
    again = compare.training_gaps(ref.follow(w, batches, 1e-3), base)
    assert again["grad_gap_median"] < 1e-3
    assert gaps["grad_gap_median"] > 30 * max(again["grad_gap_median"], 1e-4)
