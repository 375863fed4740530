"""Params named by path (``compare.named_leaves``), and the check on a
family whose reference keeps nested params."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import compare
import reference


def test_flat_dict_keeps_its_names():
    tree = {"fc01_w": np.ones((3, 2)), "fc00_w": np.zeros(4)}
    named = compare.named_leaves(tree)
    assert sorted(named) == ["fc00_w", "fc01_w"]
    np.testing.assert_array_equal(named["fc01_w"], tree["fc01_w"])


def test_nested_tree_is_named_by_path_and_stacked_leaves_stay_whole():
    tree = {"embed": np.ones((10, 4)),
            "blocks": ({"attn": {"wq": np.ones((3, 4, 4))},
                        "mlp": [np.ones((3, 4)), np.ones((3,))]},
                       {}),
            "final_norm": np.zeros(4)}
    named = compare.named_leaves(tree)
    assert sorted(named) == ["blocks/0/attn/wq", "blocks/0/mlp/0",
                             "blocks/0/mlp/1", "embed", "final_norm"]
    assert named["blocks/0/attn/wq"].shape == (3, 4, 4)


class Nested:
    """A family whose reference keeps the program's nested form: an
    embedding, one stacked block of two repeats, a head."""

    @staticmethod
    def init(cfg, seed):
        k = jax.random.split(jax.random.PRNGKey(seed), 3)
        d = cfg["d"]
        return {"embed": jax.random.normal(k[0], (cfg["inputs"], d)) / 4,
                "blocks": ({"w": jax.random.normal(k[1], (2, d, d)) / 4},
                           {}),
                "head": jax.random.normal(k[2], (d, cfg["classes"])) / 4}

    @staticmethod
    def loss(p, batch, cfg):
        h = batch["x"] @ p["embed"]
        for r in range(2):
            h = jnp.tanh(h @ p["blocks"][0]["w"][r])
        logits = h @ p["head"]
        picked = jnp.take_along_axis(logits, batch["y"][:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_nested_reference_is_checked_leaf_by_leaf(optimizer):
    cfg = {"inputs": 6, "d": 8, "classes": 5, "reference_block": 4}
    run = {"lr": 1e-2, "momentum": 0.9, "grad_clip": 1.0,
           "optimizer": optimizer}
    rng = np.random.default_rng(0)
    batches = [{"x": rng.standard_normal((16, 6), np.float32),
                "y": rng.integers(0, 5, 16).astype(np.int32)}
               for _ in range(3)]
    ref = reference.train_steps(Nested, cfg, run, 3, batches)
    assert sorted(ref["p1"]) == ["blocks/0/w", "embed", "head"]
    same = compare.readings(reference.train_steps(Nested, cfg, run, 3,
                                                  batches), ref)
    assert all(v == 0 for v in same.values()), same
    half = compare.readings(reference.train_steps(Nested, cfg, run, 3,
                                                  batches, fraction=0.5),
                            ref)
    assert half["change1_gap"] > 0.1 and half["loss_gap"] > 0
