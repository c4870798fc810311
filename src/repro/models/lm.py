"""Decoder / encoder LM assembly: embeddings -> stacks -> head, plus the
train/prefill/decode forward passes used by the trainer, the serve engine, and
the multi-pod dry-run.

Families (configs/base.ArchConfig.family):
  * ``lm``     — token decoder (command-r, yi, danube, smollm, rwkv6, hymba,
                 deepseek-v3, llama4-scout)
  * ``vlm``    — llava-next: stub patch embeddings prepended to token embeds
  * ``audio``  — hubert: stub frame embeddings, bidirectional encoder,
                 504-way framewise classification head (no decode step)

The A2Q regularizer ``L_reg`` accumulates through every stack and is returned
next to the logits, so ``loss = task + lambda * penalty`` needs no second tree
walk (paper Sec. 4.1 / App. B, lambda = 1e-3).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.dist.sharding import ShardingRules, constrain
from repro.nn.embedding import apply_embedding, init_embedding
from repro.nn.linear import (
    apply_linear,
    chain_report_scope,
    init_linear,
    linear_penalty,
)
from repro.nn.module import box, unbox
from repro.nn.norms import apply_norm, init_norm
from repro.nn.transformer import apply_stack, init_stack, init_stack_cache

__all__ = ["init_lm", "apply_lm", "lm_loss", "init_cache", "Runtime"]


class Runtime:
    """Static (hashable) execution context threaded through the model: mesh,
    EP axis, activation-sharding rules, beyond-paper toggles.

    ``grad_compress`` (an optional ``dist.collectives.GradCompressConfig``)
    routes the data-parallel gradient reduction of ``build_train_step``
    through the int-quantized ``compressed_psum_tree`` instead of the fp32
    all-reduce GSPMD would emit.

    ``decode_kernel`` routes paged-attention decode reads through the Pallas
    kernel (``kernels/paged_attention.py``) instead of the gathered-view jnp
    path — the TPU serving fast path.

    ``int_forward`` routes deployed (``q8``/``s8``) linears through the fused
    W8A8 integer kernel (``kernels/int_matmul.py``) instead of dequant + a
    ``compute_dtype`` dot — the integer-fast serve path the A2Q accumulator
    guarantee makes safe.

    ``int_chain`` (implies ``int_forward``) keeps activations integer
    *between* deployed linears: producers requantize in their epilogue and
    pass ``(codes, scale)`` (``nn.linear.IntAct``) to chained consumers;
    chain-break consumers fold their act-quant into the kernel prologue —
    zero standalone act-quant dispatches on the serve path.
    ``chain_report`` holds the per-call-site disposition lists from the most
    recent forward trace (see ``nn.linear.chain_report_scope``).
    """

    def __init__(self, mesh=None, ep_axis=None, rules=None, mla_absorb=False,
                 grad_compress=None, decode_kernel=False, int_forward=False,
                 int_chain=False):
        self.mesh = mesh
        self.ep_axis = ep_axis
        self.rules = rules
        self.mla_absorb = mla_absorb
        self.grad_compress = grad_compress
        self.decode_kernel = decode_kernel
        self.int_forward = int_forward or int_chain
        self.int_chain = int_chain
        self.chain_report: dict = {}

    def batch_spec(self, ndim: int) -> P:
        if self.rules is None:
            return P()
        return P(self.rules.rules.get("batch") or None, *([None] * (ndim - 1)))


def init_lm(key, arch: ArchConfig, *, stack_init=init_stack):
    """Boxed params.  ``stack_init(key, arch, stack_cfg)`` builds each layer
    stack (default :func:`nn.transformer.init_stack`); the serve path swaps
    in one that deploys each layer as it is made
    (``serve.engine.init_deployed_lm``)."""
    ks = jax.random.split(key, 8)
    params: dict = {}
    if arch.family != "audio":
        params["embed"] = init_embedding(ks[0], arch.vocab, arch.d_model)
    params["stacks"] = {
        str(i): stack_init(ks[1 + (i % 6)], arch, s) for i, s in enumerate(arch.stacks)
    }
    params["final_norm"] = init_norm(arch.d_model, arch.norm)
    if arch.family == "audio":
        params["head"] = init_linear(
            ks[7], arch.d_model, arch.n_classes, arch.quant,
            axes=("embed", None), boundary=True,
        )
    elif not arch.tie_embeddings:
        params["head"] = init_linear(
            ks[7], arch.d_model, arch.vocab, arch.quant,
            axes=("embed", "vocab"), boundary=True,
        )
    if arch.mtp_depth > 0:
        from repro.configs.base import StackConfig

        mtp_stack = arch.stacks[-1]
        params["mtp"] = {
            "proj": init_linear(ks[6], 2 * arch.d_model, arch.d_model, arch.quant,
                                axes=(None, "embed")),
            "block": init_stack(
                jax.random.fold_in(ks[6], 1), arch,
                StackConfig(kind="attn_mlp", count=1, attn=mtp_stack.attn,
                            d_ff=mtp_stack.d_ff or arch.d_model * 4,
                            mlp_gated=True),
            ),
            "norm_h": init_norm(arch.d_model, arch.norm),
            "norm_e": init_norm(arch.d_model, arch.norm),
        }
    return params


def _head_logits(params, arch: ArchConfig, h: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    cd = jnp.dtype(arch.compute_dtype)
    if arch.tie_embeddings and arch.family != "audio":
        logits = h.astype(cd) @ params["embed"]["table"].astype(cd).T
    else:
        logits = apply_linear(
            params["head"], h, arch.quant, boundary=True, compute_dtype=cd,
            int_forward=rt.int_forward, int_chain=rt.int_chain, site="head",
        )
    if rt.mesh is not None:
        batch = rt.rules.rules.get("batch") or ()
        # vocab axes minus any axis already carrying the batch dim (tp_extra
        # widens vocab onto 'data', which may also be the batch axis)
        vocab = tuple(a for a in (rt.rules.rules.get("vocab") or ()) if a not in batch)
        vspec = vocab[0] if len(vocab) == 1 else (tuple(vocab) if vocab else None)
        bspec = batch if batch else None
        if arch.family != "audio" and vocab and arch.vocab % _axis_prod(rt.mesh, vocab) == 0:
            logits = constrain(logits, rt.mesh, P(bspec, None, vspec))
        else:
            logits = constrain(logits, rt.mesh, P(bspec, None, None))
    return logits


def _axis_prod(mesh, axes) -> int:
    out = 1
    for a in axes or ():
        out *= mesh.shape[a]
    return out


def apply_lm(
    params: dict,
    arch: ArchConfig,
    *,
    tokens: Optional[jnp.ndarray] = None,
    frontend_embeds: Optional[jnp.ndarray] = None,
    cache: Optional[dict] = None,
    start_pos: Optional[jnp.ndarray] = None,
    rt: Optional[Runtime] = None,
    return_hidden: bool = False,
):
    """Forward pass.  ``cache`` given => cached step: ``tokens (B, T)`` with
    ``T == 1`` (decode) or ``T > 1`` (chunked prefill), written at each row's
    ``start_pos``.  A paged cache carries its block-table view under the
    reserved key ``"_paged"`` (see ``serve/paged_cache.py``); the returned
    cache holds only the per-stack state — the caller re-attaches the view.

    Returns (logits, new_cache, penalty[, hidden]).
    """
    rt = rt or Runtime()
    cd = jnp.dtype(arch.compute_dtype)

    parts = []
    if frontend_embeds is not None:
        parts.append(frontend_embeds.astype(cd))
    if tokens is not None:
        with jax.named_scope("embed"):
            parts.append(apply_embedding(params["embed"], tokens, dtype=cd))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    B, S, _ = x.shape
    x = constrain(x, rt.mesh, rt.batch_spec(3))

    view = cache.get("_paged") if cache is not None else None
    if cache is not None:
        assert start_pos is not None
        sp = jnp.asarray(start_pos, jnp.int32).reshape(-1)  # scalar or per-row (B,)
        base = sp[:, None] if sp.shape[0] == B else sp.reshape(1, 1)
        positions = jnp.broadcast_to(
            base + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
        )
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    penalty = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    # the chain report is (re)populated at trace time: each jitted forward
    # traces every apply_linear call site once, so after compilation the
    # report lists exactly what the compiled program dispatches per step
    with contextlib.ExitStack() as _scope:
        if rt.int_forward:
            _scope.enter_context(chain_report_scope(rt.chain_report))
        for i, s in enumerate(arch.stacks):
            sp = params["stacks"][str(i)]
            sc = cache.get(str(i)) if cache is not None else None
            x, nc, pen = apply_stack(
                sp, x, arch, s, positions, sc,
                mesh=rt.mesh, rules=rt.rules, ep_axis=rt.ep_axis, mla_absorb=rt.mla_absorb,
                view=view, decode_kernel=rt.decode_kernel,
                int_forward=rt.int_forward, int_chain=rt.int_chain,
            )
            x = constrain(x, rt.mesh, rt.batch_spec(3))
            if nc is not None:
                new_cache[str(i)] = nc
            penalty = penalty + pen

        h = apply_norm(params["final_norm"], x, kind=arch.norm, eps=arch.norm_eps)
        if "head" in params:
            penalty = penalty + linear_penalty(params["head"], arch.quant, True, True)
        with jax.named_scope("head"):
            logits = _head_logits(params, arch, h, rt)
    out_cache = new_cache if cache is not None else None
    if return_hidden:
        return logits, out_cache, penalty, h
    return logits, out_cache, penalty


def _cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray, z_loss: float = 1e-4):
    """Mean CE over all positions, fp32, with MaxText-style z-loss."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    ce = (lse - gold).mean()
    zl = z_loss * jnp.square(lse).mean()
    return ce + zl, ce


def lm_loss(params, arch: ArchConfig, batch: dict, rt: Optional[Runtime] = None):
    """Training loss: task CE + lambda * L_reg (+ MTP auxiliary).

    ``batch`` = {tokens [, frontend_embeds], targets} with targets aligned to
    the *full* (frontend + text) sequence.
    """
    rt = rt or Runtime()
    logits, _, penalty, h = apply_lm(
        params, arch,
        tokens=batch.get("tokens"),
        frontend_embeds=batch.get("frontend_embeds"),
        rt=rt,
        return_hidden=True,
    )
    targets = batch["targets"]
    with jax.named_scope("loss"):
        loss, ce = _cross_entropy(logits, targets)

    metrics = {"ce": ce, "penalty": penalty}
    if arch.mtp_depth > 0 and "mtp" in params:
        # DeepSeek-style MTP: predict target[t+1] from h[t] fused with the
        # embedding of target[t] (the token one step ahead of position t).
        cd = jnp.dtype(arch.compute_dtype)
        mtp = params["mtp"]
        emb_next = apply_embedding(params["embed"], targets[:, :-1], dtype=cd)
        fused = jnp.concatenate(
            [
                apply_norm(mtp["norm_h"], h[:, :-1], kind=arch.norm),
                apply_norm(mtp["norm_e"], emb_next, kind=arch.norm),
            ],
            axis=-1,
        )
        hm = apply_linear(mtp["proj"], fused, arch.quant, compute_dtype=cd)
        Bm, Sm, _ = hm.shape
        pos = jnp.broadcast_to(jnp.arange(Sm, dtype=jnp.int32)[None], (Bm, Sm))
        hm, _, mtp_pen = apply_stack(
            mtp["block"], hm, arch, _mtp_stackcfg(arch), pos, None, mesh=rt.mesh,
            rules=rt.rules,
        )
        mtp_logits = _head_logits(params, arch, hm, rt)
        mtp_loss, _ = _cross_entropy(mtp_logits, targets[:, 1:])
        loss = loss + 0.3 * mtp_loss
        penalty = penalty + mtp_pen
        metrics["mtp_ce"] = mtp_loss

    loss = loss + arch.quant.reg_lambda * penalty
    metrics["loss"] = loss
    return loss, metrics


def _mtp_stackcfg(arch: ArchConfig):
    from repro.configs.base import StackConfig

    last = arch.stacks[-1]
    return StackConfig(kind="attn_mlp", count=1, attn=last.attn,
                       d_ff=last.d_ff or arch.d_model * 4, mlp_gated=True)


def init_cache(arch: ArchConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
    """Decode caches for every stack, keyed like params['stacks']."""
    return {
        str(i): init_stack_cache(arch, s, batch, max_seq, dtype)
        for i, s in enumerate(arch.stacks)
    }
