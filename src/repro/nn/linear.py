"""QuantLinear / QuantConv — every matmul-bearing layer in the framework.

The paper's technique is a first-class mode of this layer:

* ``mode='none'`` — float weights (the floating-point baseline),
* ``mode='qat'``  — baseline quantization-aware training (paper Sec. 2.1):
  per-channel weight scales, per-tensor activation scales, z=0, half-way
  rounding, STE,
* ``mode='a2q'``  — accumulator-aware quantization (paper Sec. 4): l1
  weight-normalized reparameterization (v, t, d), norm cap from the target
  accumulator width P, round-toward-zero.  ``penalty()`` exposes the layer's
  regularizer term.

Hidden layers use (M, N, P) from :class:`~repro.configs.base.QuantConfig`;
layers flagged ``boundary=True`` (first/last) stay at 8-bit as in App. B.
``input_signed`` reflects the preceding nonlinearity (ReLU -> unsigned).

Deployment: ``deploy_linear`` converts a trained A2Q layer to (int8 weights,
per-channel scale) — the artifact whose l1 norm provably fits the P-bit
accumulator — used by the serve path and by the int8-weight-storage roofline
lever.

Integer-fast serving: with ``int_forward=True`` (``Runtime(int_forward=...)``
/ ``--int-forward``) a deployed layer skips the dequant + bf16 dot and runs
``act_quant(x) -> int8 @ int8 -> int32 -> scaled output`` through the fused
W8A8 kernel (``kernels/int_matmul.py``), with the int16 partial-sum spill
engaged automatically when the layer's A2Q ``acc_bits <= 16`` — the paper's
guarantee is exactly what makes both the integer accumulation and the narrow
carry safe on the serve path.

Int8-out chaining (``int_chain=True`` / ``--int-chain``): deployed layers
pass integer activations directly instead of round-tripping through fp32
between every pair of linears.

* A producer whose consumer is chain-eligible (``chain_out_aq`` returns the
  consumer's quantizer descriptor) requantizes in its own epilogue and
  returns an :class:`IntAct` — ``(codes int8, scale, bits, signed)`` —
  killing the consumer's standalone act-quant dispatch *and* the fp32
  activation materialization between them.
* At chain-break points (residual adds, norms, attention cores — anywhere
  the fp32 value is needed) the consumer instead folds its act-quant into
  the kernel *prologue* (``aq_scale``): the fp32 input is quantized
  in-register, so no deployed linear anywhere on the serve path pays a
  standalone act-quant dispatch.
* Unsigned 8-bit activations (rwkv6's post-relu² channel-mix ``wv``) ride
  the fused path via signed symmetrization: codes travel as ``q - 128`` and
  the kernel adds ``128 * colsum(w)`` back at flush — exact in int32.

Every apply_linear call site reports its disposition (``folded`` /
``chained`` / ``standalone`` / ``fallback``) into the active
``chain_report_scope`` at trace time; the serve engine exposes the counts as
stats-contract fields (``int_chain_requant_dispatches`` must be 0 when
chaining is on — CI-gated).
"""

from __future__ import annotations

import contextlib
import functools
import warnings
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core.a2q import a2q_int_weights, a2q_norm_cap, apply_a2q, init_a2q
from repro.core.quantizers import (
    act_quant_int,
    apply_act_quant,
    apply_weight_qat,
    init_act_quant,
    init_weight_qat,
    weight_qat_int,
)
from repro.nn.module import Boxed, box, kaiming

__all__ = [
    "init_linear",
    "apply_linear",
    "linear_penalty",
    "deploy_linear",
    "init_conv",
    "apply_conv",
    "IntAct",
    "chain_out_aq",
    "chain_report_scope",
    "acc_probe_scope",
]


class IntAct(NamedTuple):
    """A chained integer activation: the ``(codes, scale)`` convention.

    ``codes`` are int8 with the layer-output shape; unsigned-domain codes
    (``signed=False, bits=8``) are stored *symmetrized* (``true_code - 128``)
    so they always fit the int8 MXU operand — the consuming kernel adds the
    ``128 * colsum(w)`` correction at flush.  ``scale`` is the (per-tensor)
    activation scale the codes were quantized with, i.e. the *consumer's*
    ``exp2(aq.log2_scale)``.
    """

    codes: jnp.ndarray
    scale: jnp.ndarray
    bits: int
    signed: bool


def _int_act_to_fp(a: IntAct, dtype) -> jnp.ndarray:
    """Re-materialize an IntAct to floating point (chain-repair fallback)."""
    q = a.codes.astype(jnp.float32)
    if not a.signed and a.bits == 8:
        q = q + 128.0
    return (q * a.scale).astype(dtype)


# --- chain-report collector ------------------------------------------------
#
# apply_linear has no Runtime handle, so call-site dispositions are collected
# through a module-level scope stack.  The scope is entered around a model
# forward (models/lm.apply_lm) and populated at *trace* time — a jitted
# forward traces each call site exactly once (the decode megastep's lax.scan
# included), so the lists are per-dispatch-site counts of what the compiled
# program actually launches.

_ACTIVE_REPORT: list = []
_WARNED: set = set()


def _fresh_report() -> dict:
    return {"folded": [], "chained": [], "standalone": [], "fallback": []}


@contextlib.contextmanager
def chain_report_scope(report: dict):
    """Collect apply_linear dispositions into ``report`` (cleared on entry).

    ``folded``     — act-quant ran inside the fused kernel (prologue or a
                     chained IntAct consumption): zero standalone dispatches.
    ``chained``    — the layer requantized in its epilogue and emitted int8
                     codes for its consumer.
    ``standalone`` — a deployed layer paid a separate act-quant dispatch
                     before the fused kernel (the unchained int-forward
                     baseline; must be empty under ``int_chain``).
    ``fallback``   — the fused path was unavailable (non-deployed params,
                     unsupported weight rank, MoE ragged experts, ...).
    """
    report.clear()
    report.update(_fresh_report())
    _ACTIVE_REPORT.append(report)
    try:
        yield report
    finally:
        _ACTIVE_REPORT.pop()


def _record(kind: str, site: str):
    if _ACTIVE_REPORT:
        _ACTIVE_REPORT[-1][kind].append(site)


# --- accumulator-headroom probe --------------------------------------------
#
# The A2Q guarantee is proved statically from the deployed weights' l1 norms;
# this probe makes it *observable*: inside an acc_probe_scope, each eager
# fused-path call samples the worst partial-sum magnitude its actual integer
# operands could produce and records it against the layer's accumulator
# bound.  The serve obs layer (obs/headroom.py) exports the samples as
# acc_headroom gauges next to the static per-channel utilization report.

_ACTIVE_ACC_PROBE: list = []


@contextlib.contextmanager
def acc_probe_scope(samples: list):
    """Sample observed accumulator magnitudes from the fused W8A8 path.

    Inside the scope, every *eager* ``_apply_linear_int8`` call appends one
    record per call site::

        {"site", "acc_max", "acc_bits", "bound", "spill_int16",
         "in_bits", "in_signed"}

    ``acc_max`` is ``max(|x_codes| @ |q8|)`` over output channels in int32 —
    an upper bound on the magnitude of *any* partial sum, in any
    accumulation order, for the actual integer operands (the runtime twin of
    the paper's Eq. 11 check, which bounds the same quantity by
    ``||w||_1 * 2**(N - 1_signed)`` over all possible inputs).  Jitted call
    sites skip the probe (their operands are tracers); ``obs/headroom.py``
    drives one eager forward to populate it.
    """
    samples.clear()
    _ACTIVE_ACC_PROBE.append(samples)
    try:
        yield samples
    finally:
        _ACTIVE_ACC_PROBE.pop()


@functools.partial(jax.jit, static_argnums=2)
def _acc_max(codes, q8, symmetrized):
    # on the device, in int32: |codes| <= 255 and each column of |q8| sums
    # to at most 127 * K, so the product stays below 2**31 for K < 66000
    xc = codes.astype(jnp.int32)
    if symmetrized:
        xc = xc + 128  # stored codes are true - 128 (unsigned-8 ride-along)
    xc = jnp.abs(xc).reshape(-1, xc.shape[-1])
    wq = jnp.abs(q8.astype(jnp.int32))
    return jnp.max(jnp.matmul(xc, wq, preferred_element_type=jnp.int32))


def _probe_acc(site, codes, q8, *, in_bits, in_signed, acc_bits, spill_int16,
               symmetrized=False):
    if not _ACTIVE_ACC_PROBE:
        return
    if isinstance(codes, jax.core.Tracer) or isinstance(q8, jax.core.Tracer):
        return  # abstract operands (jit/vmap/scan): nothing to sample
    acc_max = int(_acc_max(codes, q8, symmetrized)) if codes.size and q8.size else 0
    _ACTIVE_ACC_PROBE[-1].append({
        "site": site,
        "acc_max": acc_max,
        "acc_bits": int(acc_bits),
        "bound": 2 ** (int(acc_bits) - 1) - 1,
        "spill_int16": bool(spill_int16),
        "in_bits": int(in_bits),
        "in_signed": bool(in_signed),
    })


def _warn_fallback_once(site: str, reason: str):
    key = (site, reason)
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(
            f"int_forward fallback at {site or '<unlabeled linear>'}: {reason} "
            "(dequant path; counted in the chain report)",
            stacklevel=3,
        )


def _bits(cfg: QuantConfig, boundary: bool) -> tuple[int, int]:
    if boundary:
        return cfg.boundary_bits, cfg.boundary_bits
    return cfg.weight_bits, cfg.act_bits


def init_linear(
    key,
    d_in: int,
    d_out: int,
    cfg: QuantConfig,
    *,
    axes: Sequence[Optional[str]] = ("embed", "mlp"),
    use_bias: bool = False,
    boundary: bool = False,
    input_signed: bool = True,
    w_std: Optional[float] = None,
    act_absmax: float = 6.0,
) -> dict:
    """Weights are stored ``(d_in, d_out)`` — output channels (accumulators)
    on the last axis, matching ``core.a2q`` conventions."""
    k_w, _ = jax.random.split(key)
    if w_std is None:
        w = kaiming(k_w, (d_in, d_out), fan_in=d_in)
    else:
        w = jax.random.normal(k_w, (d_in, d_out)) * w_std
    M, N = _bits(cfg, boundary)
    out_axis = axes[-1]
    p: dict = {}
    if cfg.mode == "none":
        p["w"] = box(w, tuple(axes))
    elif cfg.mode == "qat":
        p["w"] = box(w, tuple(axes))
        wq = init_weight_qat(w, M)
        p["wq"] = {"log2_scale": box(wq["log2_scale"], (out_axis,))}
        aq = init_act_quant(N, input_signed, init_absmax=act_absmax)
        p["aq"] = {"log2_scale": box(aq["log2_scale"], ())}
    elif cfg.mode == "a2q":
        a = init_a2q(w, M, cfg.acc_bits, N, input_signed)
        p["v"] = box(a["v"], tuple(axes))
        p["t"] = box(a["t"], (out_axis,))
        p["d"] = box(a["d"], (out_axis,))
        aq = init_act_quant(N, input_signed, init_absmax=act_absmax)
        p["aq"] = {"log2_scale": box(aq["log2_scale"], ())}
    else:
        raise ValueError(cfg.mode)
    if use_bias:
        p["b"] = box(jnp.zeros((d_out,), jnp.float32), (out_axis,))
    return p


def _quant_weights(params: dict, cfg: QuantConfig, boundary: bool, input_signed: bool):
    M, N = _bits(cfg, boundary)
    if "q8" in params:  # deployed int8 storage (beyond-paper serve lever)
        # s8 is per-output-channel; stacked leaves carry leading batch dims
        # (q8 (..., K, N), s8 (..., N)), so align it explicitly
        return params["q8"].astype(jnp.float32) * params["s8"][..., None, :]
    if cfg.mode == "none":
        return params["w"]
    if cfg.mode == "qat":
        return apply_weight_qat({"log2_scale": params["wq"]["log2_scale"]}, params["w"], M)
    if cfg.mode == "a2q":
        return apply_a2q(
            {"v": params["v"], "t": params["t"], "d": params["d"]},
            M,
            cfg.acc_bits,
            N,
            input_signed,
        )
    raise ValueError(cfg.mode)


def _int_forward_mode(params: dict, x, N: int) -> str:
    """How this call can take the fused W8A8 path: ``'fused'`` (2D weights),
    ``'vmap'`` (stacked 3D weight leaves batched over the kernel — the
    leading axes of ``x`` and ``q8`` must line up), or ``''`` (dequant
    fallback).  Needs deployed int8 storage, an activation quantizer to
    produce the int8 operand, and ``N <= 8`` — unsigned 8-bit codes ride via
    signed symmetrization (``q - 128`` + the colsum correction at flush), so
    the old ``N <= 7`` unsigned restriction is gone."""
    if "q8" not in params or "aq" not in params or N > 8:
        return ""
    q8 = params["q8"]
    if q8.ndim == 2:
        return "fused"
    xc = x.codes if isinstance(x, IntAct) else x
    if q8.ndim == 3 and xc.ndim >= 3 and xc.shape[0] == q8.shape[0]:
        return "vmap"
    return ""


def chain_out_aq(
    consumer: dict,
    cfg: QuantConfig,
    *,
    boundary: bool = False,
    input_signed: bool = True,
    act_fn: Optional[str] = None,
) -> Optional[dict]:
    """The *consumer's* activation-quantizer descriptor, if the producer can
    requantize into it (int8-out chaining).  ``None`` means the edge is a
    chain break — the consumer is not deployed / not fusable — detected
    statically from the deployed params, so the producer emits fp32 and the
    consumer falls back to its own (prologue) quantization.

    ``act_fn`` names the elementwise activation sitting *between* the two
    linears (``'relu2'`` / ``'gelu'`` / ``None``); the producer's epilogue
    replays it bit-exactly before requantizing.
    """
    N = _bits(cfg, boundary)[1]
    if "q8" not in consumer or "aq" not in consumer or N > 8:
        return None
    if consumer["q8"].ndim != 2:
        return None
    return {
        "log2_scale": consumer["aq"]["log2_scale"],
        "bits": N,
        "signed": input_signed,
        "act_fn": act_fn,
    }


def _apply_linear_int8(
    params: dict,
    x,
    cfg: QuantConfig,
    *,
    boundary: bool,
    input_signed: bool,
    compute_dtype,
    int_chain: bool = False,
    out_aq: Optional[dict] = None,
    site: str = "",
):
    """Fused W8A8 forward: one ``pallas_call`` from activations to output.
    The activation scale folds into the per-channel weight scale, so the
    epilogue is a single per-column fp32 rescale (+ bias); the int16
    partial-sum spill engages when A2Q guarantees ``acc_bits <= 16``.

    Chaining changes where the activation quantizer runs:

    * ``x`` is an :class:`IntAct` — the producer already requantized; the
      codes feed the kernel directly (``folded``: no dispatch at all here).
    * ``int_chain`` and ``x`` is fp — the quantizer folds into the kernel
      *prologue* (``folded``).
    * plain ``int_forward`` — the quantizer runs as its own dispatch ahead
      of the kernel (``standalone``), with unsigned 8-bit codes symmetrized
      into the int8 operand.

    With ``out_aq`` (the consumer's quantizer) the epilogue requantizes and
    the call returns an :class:`IntAct` instead of a float array.
    """
    from repro.kernels import ops

    M, N = _bits(cfg, boundary)
    a2q = cfg.mode == "a2q"
    kw = dict(
        acc_bits=cfg.acc_bits if a2q else 32,
        mode="exact",
        spill_int16=a2q and cfg.acc_bits <= 16,
        bias=params.get("b"),
    )
    if out_aq is not None:
        kw.update(
            out_scale=jnp.exp2(out_aq["log2_scale"].astype(jnp.float32)),
            out_bits=out_aq["bits"],
            out_signed=out_aq["signed"],
            act_fn=out_aq["act_fn"],
            cast_dtype=compute_dtype,
        )
    s8 = params["s8"].astype(jnp.float32)
    if isinstance(x, IntAct):
        # chained handoff: the producer quantized into *this* layer's aq
        _record("folded", site)
        codes, x_scale = x.codes, x.scale
        _probe_acc(site, codes, params["q8"], in_bits=x.bits, in_signed=x.signed,
                   acc_bits=kw["acc_bits"], spill_int16=kw["spill_int16"],
                   symmetrized=not x.signed and x.bits == 8)
        K = codes.shape[-1]
        lead = codes.shape[:-1]
        y = ops.int_matmul(
            codes.reshape(-1, K), params["q8"],
            scale=x_scale * s8, in_bits=x.bits, in_signed=x.signed, **kw,
        )
    elif int_chain:
        # chain break: fold the act-quant into the kernel prologue
        _record("folded", site)
        x_scale = jnp.exp2(params["aq"]["log2_scale"].astype(jnp.float32))
        if _ACTIVE_ACC_PROBE and not isinstance(x, jax.core.Tracer):
            # replay the prologue's quantization so the probe sees the exact
            # codes the kernel folds in-register
            xq_p, _ = act_quant_int(
                {"log2_scale": params["aq"]["log2_scale"]},
                x.astype(jnp.float32), N, signed=input_signed,
            )
            _probe_acc(site, xq_p, params["q8"], in_bits=N, in_signed=input_signed,
                       acc_bits=kw["acc_bits"], spill_int16=kw["spill_int16"])
        K = x.shape[-1]
        lead = x.shape[:-1]
        y = ops.int_matmul(
            x.astype(jnp.float32).reshape(-1, K), params["q8"],
            scale=x_scale * s8, aq_scale=x_scale,
            in_bits=N, in_signed=input_signed, **kw,
        )
    else:
        # unchained int forward: the act-quant is its own dispatch
        _record("standalone", site)
        with jax.named_scope("act_quant"):
            xq, x_scale = act_quant_int(
                {"log2_scale": params["aq"]["log2_scale"]},
                x.astype(jnp.float32), N, signed=input_signed,
            )
        _probe_acc(site, xq, params["q8"], in_bits=N, in_signed=input_signed,
                   acc_bits=kw["acc_bits"], spill_int16=kw["spill_int16"])
        if not input_signed and N == 8:
            xq = xq - 128.0  # symmetrize u8 codes into the int8 operand
        K = x.shape[-1]
        lead = x.shape[:-1]
        y = ops.int_matmul(
            xq.astype(jnp.int8).reshape(-1, K), params["q8"],
            scale=x_scale * s8, in_bits=N, in_signed=input_signed, **kw,
        )
    if out_aq is not None:
        _record("chained", site)
        return IntAct(
            codes=y.reshape(*lead, y.shape[-1]),
            scale=jnp.exp2(out_aq["log2_scale"].astype(jnp.float32)),
            bits=out_aq["bits"],
            signed=out_aq["signed"],
        )
    return y.reshape(*lead, y.shape[-1]).astype(compute_dtype)


def apply_linear(
    params: dict,
    x,
    cfg: QuantConfig,
    *,
    boundary: bool = False,
    input_signed: bool = True,
    compute_dtype=jnp.bfloat16,
    int_forward: bool = False,
    int_chain: bool = False,
    out_aq: Optional[dict] = None,
    site: str = "",
):
    """``y = act_quant(x) @ quant(w) (+ b)`` in ``compute_dtype``.

    ``int_forward=True`` on a deployed layer (``q8``/``s8`` present) runs the
    fused W8A8 integer path instead of dequant + ``compute_dtype`` dot.
    ``int_chain=True`` additionally folds the activation quantizer into the
    kernel (prologue at chain breaks, the producer's epilogue on chained
    edges); ``x`` may then be an :class:`IntAct`, and with ``out_aq`` (from
    :func:`chain_out_aq`) the result is one too.  ``site`` labels this call
    in the active chain report.
    """
    M, N = _bits(cfg, boundary)
    mode = _int_forward_mode(params, x, N) if int_forward else ""
    if mode == "fused":
        return _apply_linear_int8(
            params, x, cfg,
            boundary=boundary, input_signed=input_signed,
            compute_dtype=compute_dtype, int_chain=int_chain,
            out_aq=out_aq, site=site,
        )
    if mode == "vmap":
        # stacked weight leaves (vmapped layer stacks): batch the fused
        # kernel over the leading axis — jax.vmap batches the pallas_call
        fn = lambda p, xi: _apply_linear_int8(
            p, xi, cfg,
            boundary=boundary, input_signed=input_signed,
            compute_dtype=compute_dtype, int_chain=int_chain, site=site,
        )
        return jax.vmap(fn)(params, x)
    if int_forward and "q8" in params:
        if "aq" not in params:
            reason = "no activation quantizer in the deployed params"
        elif N > 8:
            reason = f"act bits N={N} > 8"
        else:
            reason = (f"stacked weight leaves (rank {params['q8'].ndim}) "
                      "without a matching batched input")
        _warn_fallback_once(site, reason)
        _record("fallback", site)
    if isinstance(x, IntAct):
        # chain repair: the consumer can't take codes — re-materialize fp
        _record("fallback", site)
        x = _int_act_to_fp(x, compute_dtype)
    if cfg.mode != "none" and "aq" in params:
        with jax.named_scope("act_quant"):
            x = apply_act_quant(
                {"log2_scale": params["aq"]["log2_scale"]}, x, N, signed=input_signed
            )
    with jax.named_scope("a2q_weight_quant"):
        w = _quant_weights(params, cfg, boundary, input_signed).astype(compute_dtype)
    y = jnp.dot(x.astype(compute_dtype), w)
    if "b" in params:
        y = y + params["b"].astype(compute_dtype)
    return y


def linear_penalty(params: dict, cfg: QuantConfig, boundary: bool, input_signed: bool) -> jnp.ndarray:
    """This layer's ``R_l = sum_i max(t_i - T_i, 0)`` (zero unless a2q)."""
    if cfg.mode != "a2q" or "t" not in params:
        return jnp.zeros((), jnp.float32)
    _, N = _bits(cfg, boundary)
    with jax.named_scope("a2q_weight_quant"):
        T = a2q_norm_cap(params["d"], cfg.acc_bits, N, input_signed)
        return jnp.sum(jnp.maximum(params["t"] - T, 0.0))


def deploy_linear(params: dict, cfg: QuantConfig, *, boundary: bool = False, input_signed: bool = True) -> dict:
    """A2Q/QAT layer -> inference artifacts {q8 int8, s8 scale [, b, aq]}."""
    M, N = _bits(cfg, boundary)
    if cfg.mode == "a2q":
        q, s = a2q_int_weights(
            {"v": params["v"], "t": params["t"], "d": params["d"]},
            M,
            cfg.acc_bits,
            N,
            input_signed,
        )
    elif cfg.mode == "qat":
        q, s = weight_qat_int({"log2_scale": params["wq"]["log2_scale"]}, params["w"], M)
    else:
        raise ValueError("deploy requires a quantized mode")
    out = {"q8": q.astype(jnp.int8), "s8": s.astype(jnp.float32)}
    if "b" in params:
        out["b"] = params["b"]
    if "aq" in params:
        out["aq"] = params["aq"]
    return out


# ---------------------------------------------------------------------------
# Conv (vision benchmarks: MobileNetV1 / ResNet18 / ESPCN / UNet)
# ---------------------------------------------------------------------------


def init_conv(
    key,
    c_in: int,
    c_out: int,
    kernel: tuple[int, int],
    cfg: QuantConfig,
    *,
    groups: int = 1,
    use_bias: bool = False,
    boundary: bool = False,
    input_signed: bool = False,  # vision nets are ReLU nets -> unsigned acts
) -> dict:
    """HWIO weights ``(kh, kw, c_in/groups, c_out)`` — channel axis last, so
    A2Q's per-output-channel reduction (= per accumulator, K = kh*kw*c_in/g)
    applies unchanged."""
    kh, kw = kernel
    fan_in = kh * kw * (c_in // groups)
    w = kaiming(key, (kh, kw, c_in // groups, c_out), fan_in=fan_in)
    axes = (None, None, None, "conv_out")
    M, N = _bits(cfg, boundary)
    p: dict = {}
    if cfg.mode == "none":
        p["w"] = box(w, axes)
    elif cfg.mode == "qat":
        p["w"] = box(w, axes)
        p["wq"] = {"log2_scale": box(init_weight_qat(w, M)["log2_scale"], ("conv_out",))}
        p["aq"] = {"log2_scale": box(init_act_quant(N, input_signed)["log2_scale"], ())}
    elif cfg.mode == "a2q":
        a = init_a2q(w, M, cfg.acc_bits, N, input_signed)
        p["v"] = box(a["v"], axes)
        p["t"] = box(a["t"], ("conv_out",))
        p["d"] = box(a["d"], ("conv_out",))
        p["aq"] = {"log2_scale": box(init_act_quant(N, input_signed)["log2_scale"], ())}
    if use_bias:
        p["b"] = box(jnp.zeros((c_out,), jnp.float32), ("conv_out",))
    return p


def apply_conv(
    params: dict,
    x: jnp.ndarray,
    cfg: QuantConfig,
    *,
    stride: tuple[int, int] = (1, 1),
    padding: str = "SAME",
    groups: int = 1,
    boundary: bool = False,
    input_signed: bool = False,
    compute_dtype=jnp.float32,
) -> jnp.ndarray:
    """NHWC convolution with the same quant pipeline as apply_linear."""
    M, N = _bits(cfg, boundary)
    if cfg.mode != "none" and "aq" in params:
        with jax.named_scope("act_quant"):
            x = apply_act_quant(
                {"log2_scale": params["aq"]["log2_scale"]}, x, N, signed=input_signed
            )
    with jax.named_scope("a2q_weight_quant"):
        w = _quant_weights(params, cfg, boundary, input_signed).astype(compute_dtype)
    y = jax.lax.conv_general_dilated(
        x.astype(compute_dtype),
        w,
        window_strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    if "b" in params:
        y = y + params["b"].astype(compute_dtype)
    return y
