"""The plain float32 references agree with the program where the program
computes in float32 too: at the two-layer size, with the program's compute
type set to float32, what is left between them is summation order."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import core
from bench.model import arch_from_config, make_params
from bench.reference import llama, train as ref
from bench.tests.tiny import DATA


def _cfg(name):
    return dict(core.load_json(DATA / f"{name}.config.json"), compute_dtype="float32")


@pytest.mark.parametrize("seed", [1, 2**33 + 7])
def test_serving_reference_matches_program_logits(seed):
    from repro.models.lm import Runtime, apply_lm

    cfg = dict(_cfg("tiny-serve"), kv_bits=0)  # the program's forward pass keeps no cache
    arch = arch_from_config(cfg)
    params = make_params(cfg, seed, deployed=True)
    toks = np.random.default_rng(seed).integers(0, cfg["vocab_size"], 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = apply_lm(params, arch, tokens=jnp.asarray(toks[None]), rt=Runtime())
    served = np.asarray(jnp.argmax(logits[0, 9:], axis=-1))  # the program's greedy picks
    gaps = llama.gaps(params, cfg, toks, 10, served[: len(toks) - 9])
    assert gaps.shape == (len(toks) - 9,)
    assert float(np.max(gaps)) < 1e-4
    # and a token the program did not pick lies below the best
    other = (served + 1) % cfg["vocab_size"]
    assert float(np.max(llama.gaps(params, cfg, toks, 10, other[: len(toks) - 9]))) > 1e-3


@pytest.mark.parametrize("seed", [3, 2**32 + 1])
def test_training_reference_matches_program_gradients(seed):
    from repro.models.lm import lm_loss

    cfg = _cfg("tiny-train")
    arch = arch_from_config(cfg)
    params = make_params(cfg, seed, deployed=False)
    x = np.random.default_rng(seed).integers(0, cfg["vocab_size"], (4, 17)).astype(np.int32)
    tokens, targets = jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(
            lambda p: lm_loss(p, arch, {"tokens": tokens, "targets": targets}), has_aux=True)(params)
        lr, gr = ref.grads(params, cfg, tokens, targets, rows_per_block=2)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        na, nb = float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b))
        assert abs(na - nb) <= 1e-3 * nb + 1e-9


def test_training_reference_step_is_adamw():
    cfg = _cfg("tiny-train")
    params = make_params(cfg, 5, deployed=False)
    x = np.random.default_rng(5).integers(0, cfg["vocab_size"], (2, 9)).astype(np.int32)
    batch = {"tokens": jnp.asarray(x[:, :-1]), "targets": jnp.asarray(x[:, 1:])}
    state = ref.init_state(params)
    state["step"] = jnp.int32(cfg["train"]["warmup"])  # the peak learning rate
    new, _, g = ref.step(state, batch, cfg, rows_per_block=1)
    lr, eps = cfg["train"]["lr"], cfg["train"]["eps"]
    # first AdamW step: m_hat = g, v_hat = g^2, so each weight moves by lr * g / (|g| + eps)
    for p0, p1, gi in zip(jax.tree.leaves(params), jax.tree.leaves(new["params"]), jax.tree.leaves(g)):
        np.testing.assert_allclose(np.asarray(p0 - p1), np.asarray(lr * gi / (jnp.abs(gi) + eps)),
                                   rtol=1e-4, atol=2e-6)
