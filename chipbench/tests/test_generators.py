"""The inputs a run makes from --seed: the same seed gives the same inputs,
another seed other inputs; seeds beyond 32 bits work."""
import numpy as np

from chipbench import rng, run, weights
from chipbench.tests import cells
from chipbench.windows import serve, train


def ctx(seed, kind="train"):
    cell = {"train": "train.opt-1.3b.c16", "serve": "serve.opt-1.3b.live"}[kind]
    entry = cells.entry(cell)
    wl = run.load_json("workloads", entry["traffic"] + ".json")
    if kind == "train":
        wl = {**wl, "feed_batches": 2, "clients": 2}
    conf = run.load_json("configs", entry["config"] + ".json")
    return run.Ctx(cell, wl, conf, seed, 10.0, None, {}, 1)


SEEDS = (5, 2**31 + 77, 2**40 + 3)


def test_train_feed():
    a, b, c = (np.asarray(train.feed_maker(ctx(s))(train.feed_key(s)))
               for s in (SEEDS[1], SEEDS[1], SEEDS[2]))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    # every row of every client and batch differs
    rows = a.reshape(-1, a.shape[-1])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_serve_rounds_and_prompts():
    r1, r2, r3 = (serve.make_rounds(ctx(s, "serve"))
                  for s in (SEEDS[0], SEEDS[0], SEEDS[1]))
    for (s1, c1, t1), (s2, c2, t2) in zip(r1, r2):
        assert np.array_equal(s1, s2) and np.array_equal(c1, c2)
        assert np.array_equal(t1, t2)
    assert not np.array_equal(r1[0][0], r3[0][0])
    # sender steps advance one per round and cross τ inside the window
    steps = np.stack([t for _, _, t in r1])[:, 0]
    assert (np.diff(steps) == 1).all()
    c = ctx(SEEDS[0], "serve")
    due = int(c.seconds * 1000 / c.workload["round_ms"])
    assert steps[0] < c.workload["tau"] <= steps[due - 1]
    p = [serve.prompt(ctx(s, "serve"), 128, 3) for s in SEEDS]
    assert np.array_equal(p[0], serve.prompt(ctx(SEEDS[0], "serve"), 128, 3))
    assert not np.array_equal(p[0], p[1])
    assert not np.array_equal(p[0], serve.prompt(ctx(SEEDS[0], "serve"),
                                                 128, 4))


def test_weights_and_keys():
    import jax
    import jax.numpy as jnp
    tree = {"embed": {"tok": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)},
            "g0": {"s0": {"wq": jax.ShapeDtypeStruct((2, 4, 4), jnp.bfloat16),
                          "bq": jax.ShapeDtypeStruct((2, 4), jnp.bfloat16)}}}
    w = [weights.make(tree, s, jnp.bfloat16) for s in (SEEDS[2], SEEDS[2], 6)]
    eq = jax.tree.map(lambda a, b: bool((a == b).all()), w[0], w[1])
    assert all(jax.tree.leaves(eq))
    ne = jax.tree.map(lambda a, b: bool((a != b).any()), w[0], w[2])
    assert all(jax.tree.leaves(ne))
    assert rng.seed32(SEEDS[2], 1) != rng.seed32(SEEDS[1], 1)
