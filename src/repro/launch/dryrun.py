import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402  (the two lines above MUST precede any jax-touching import)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell against
the production mesh and record memory / cost / collective analyses.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 v5e pods;
``jax.jit(step).lower(...).compile()`` must succeed for every cell, and the
compiled artifact supplies the roofline terms (EXPERIMENTS.md SDry-run /
SRoofline).

Costing methodology: XLA's cost_analysis counts a while-loop (lax.scan) body
ONCE, not x trip-count (verified in tests/test_roofline.py), so the scanned
full graph underreports per-step cost.  The roofline numbers are therefore
reconstructed by *marginal-layer extrapolation*: for every distinct stack
signature we compile unrolled 1-layer and 2-layer variants and take

    total = cost(base: one layer per signature)
          + sum_entries (count_e - 1) * [cost(sig 2-layer) - cost(base)]

which is exact for homogeneous scanned stacks (every layer in a stack has
identical cost by construction).  The full scanned graph is still compiled for
every cell — that compile succeeding IS the dry-run pass, and supplies
memory_analysis + the collective schedule.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-v3-671b \
        --shape decode_32k --opt mla_absorb --tag hc_mla

Results land in experiments/dryrun/<tag>/<arch>__<shape>__<mesh>.json.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, applicable_shapes, get_arch, input_specs
from repro.dist.collectives import GradCompressConfig, resolve_grad_compress
from repro.dist.sharding import ShardingRules, cache_specs, param_specs
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.lm import Runtime, init_cache, init_lm
from repro.models.steps import build_prefill_step, build_serve_step, build_train_step
from repro.nn.module import unbox
from repro.optim.optimizers import adafactor
from repro.roofline.analysis import (
    collective_bytes_from_hlo,
    model_flops,
    roofline_terms,
    wire_bytes,
)

_COST_KEYS = ("flops", "bytes accessed", "transcendentals")


def _sharding(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def _param_counts(boxed_shapes, arch) -> dict:
    total = 0
    routed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(unbox(boxed_shapes))[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        ks = jax.tree_util.keystr(path)
        if "moe" in ks and any(k in ks for k in ("'w_in'", "'w_gate'", "'w_out'")):
            routed += n
    active = total
    for s in arch.stacks:
        if s.moe is not None and routed:
            active = total - routed + routed * s.moe.top_k / s.moe.n_experts
            break
    return {"total": total, "active": active, "routed": routed}


def _make_runtime(arch, mesh, opts):
    rules = ShardingRules.default(
        mesh, arch, fsdp="no_fsdp" not in opts,
        seq_shard_extra="seq_shard_extra" in opts, tp_extra="tp_extra" in opts,
    )
    ep_axis = None
    if any(s.moe is not None for s in arch.stacks):
        # 'ep_both': experts over (model, data) — 1 expert/chip serving layout
        ep_axis = ("model", "data") if "ep_both" in opts else "model"
    grad_compress = None
    if "grad_compress" in opts:
        grad_compress = GradCompressConfig(
            bits=8,
            scale_axis="column" if "grad_compress_column" in opts else "tensor",
        )
    rt = Runtime(
        mesh=mesh, ep_axis=ep_axis, rules=rules,
        mla_absorb="mla_absorb" in opts, grad_compress=grad_compress,
    )
    return rules, rt


def _lower_compile(arch, shape, mesh, rules, rt, opts=frozenset()) -> dict:
    """Lower + compile one step function; return cost/collective/memory info."""
    key = jax.random.PRNGKey(0)
    boxed_shapes = jax.eval_shape(lambda: init_lm(key, arch))
    if "int8_weights" in opts and shape.kind != "train":
        # A2Q-guaranteed int8 weight deployment (beyond-paper memory lever)
        from repro.serve.engine import deploy_boxed

        boxed_shapes = deploy_boxed(boxed_shapes, arch.quant)
    pspecs = param_specs(boxed_shapes, mesh, rules)
    param_shapes = unbox(boxed_shapes)
    counts = _param_counts(boxed_shapes, arch)
    batch_specs = input_specs(arch, shape)

    def bspec(shape_tuple):
        # divisibility-aware: long_500k's global_batch=1 falls back to
        # replicated instead of an invalid P('data') spec
        from repro.dist.sharding import resolve_pspec

        axes = ("batch",) + (None,) * (len(shape_tuple) - 1)
        return resolve_pspec(axes, shape_tuple, mesh, rules)

    batch_sharding = {k: NamedSharding(mesh, bspec(v.shape)) for k, v in batch_specs.items()}

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            optimizer = adafactor()
            opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
            from repro.train.state import init_grad_err, make_state_specs

            gc = resolve_grad_compress(rt.grad_compress, mesh)
            state_spec = make_state_specs(boxed_shapes, optimizer, mesh, rules, grad_compress=gc)
            state_shapes = {
                "params": param_shapes,
                "opt_state": opt_shapes,
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            if gc is not None:
                state_shapes["grad_err"] = jax.eval_shape(
                    lambda: init_grad_err(
                        param_shapes, mesh.shape[gc.axis], pspecs=pspecs, axis=gc.axis
                    )
                )
            jitted = jax.jit(
                build_train_step(arch, optimizer, rt),
                in_shardings=(_sharding(mesh, state_spec), batch_sharding),
                out_shardings=(_sharding(mesh, state_spec), None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_shapes, batch_specs)
        elif shape.kind == "prefill":
            jitted = jax.jit(
                build_prefill_step(arch, rt),
                in_shardings=(_sharding(mesh, pspecs), batch_sharding),
            )
            lowered = jitted.lower(param_shapes, batch_specs)
        else:  # decode
            cache_shapes = jax.eval_shape(
                lambda: init_cache(arch, shape.global_batch, shape.seq_len, jnp.bfloat16)
            )
            cspecs = cache_specs(cache_shapes, mesh, rules)
            jitted = jax.jit(
                build_serve_step(arch, rt),
                in_shardings=(
                    _sharding(mesh, pspecs),
                    NamedSharding(mesh, bspec(batch_specs["tokens"].shape)),
                    _sharding(mesh, cspecs),
                    NamedSharding(mesh, P()),
                ),
                out_shardings=(None, _sharding(mesh, cspecs)),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                param_shapes,
                batch_specs["tokens"],
                cache_shapes,
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1

    info = {"lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2), "counts": counts}
    try:
        mem = compiled.memory_analysis()
        info["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
    except Exception as e:
        info["memory_analysis"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        info["cost"] = {k: float(ca.get(k, 0.0)) for k in _COST_KEYS}
    except Exception as e:
        info["cost"] = {k: 0.0 for k in _COST_KEYS}
        info["cost_error"] = str(e)
    hlo = compiled.as_text()
    info["hlo_bytes"] = len(hlo)
    info["collectives"] = collective_bytes_from_hlo(hlo)
    del hlo, compiled, lowered
    return info


def _stack_signature(s):
    return dataclasses.replace(s, count=1)


def _costing_variants(arch):
    """(base arch, {sig: variant arch}, entry signatures) for extrapolation."""
    sigs = []
    seen = {}
    for s in arch.stacks:
        sig = _stack_signature(s)
        sigs.append(sig)
        seen.setdefault(sig, None)
    distinct = list(seen.keys())
    base = dataclasses.replace(arch, stacks=tuple(distinct), unroll_stacks=True)
    variants = {}
    for sig in distinct:
        stacks = tuple(
            dataclasses.replace(d, count=2) if d == sig else d for d in distinct
        )
        variants[sig] = dataclasses.replace(arch, stacks=stacks, unroll_stacks=True)
    return base, variants, sigs


def _combine(base_info, variant_infos, sigs, counts_per_entry) -> dict:
    """total = base + sum_entries (count-1) * (variant[sig] - base)."""
    out_cost = dict(base_info["cost"])
    out_coll = {
        "total_bytes": base_info["collectives"]["total_bytes"],
        "bytes_by_kind": dict(base_info["collectives"]["bytes_by_kind"]),
    }
    for sig, count in zip(sigs, counts_per_entry):
        v = variant_infos[sig]
        extra = count - 1
        if extra <= 0:
            continue
        for k in _COST_KEYS:
            out_cost[k] += extra * (v["cost"][k] - base_info["cost"][k])
        out_coll["total_bytes"] += extra * (
            v["collectives"]["total_bytes"] - base_info["collectives"]["total_bytes"]
        )
        for kind in out_coll["bytes_by_kind"]:
            out_coll["bytes_by_kind"][kind] += extra * (
                v["collectives"]["bytes_by_kind"][kind]
                - base_info["collectives"]["bytes_by_kind"][kind]
            )
    return {"cost": out_cost, "collectives": out_coll}


def run_cell(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    opts: Optional[set] = None,
    out_dir: str = "experiments/dryrun",
    tag: str = "baseline",
    costing: bool = True,
) -> dict:
    opts = opts or set()
    arch = get_arch(arch_name)
    if "remat_none" in opts:
        arch = dataclasses.replace(arch, remat="none")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules, rt = _make_runtime(arch, mesh, opts)

    record = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": mesh.size,
        "opts": sorted(opts),
        "tag": tag,
    }

    # 1) the required dry-run pass: full scanned graph must lower + compile
    full = _lower_compile(arch, shape, mesh, rules, rt, opts)

    # 1b) train cells with grad_compress ON price the compressed-gradient
    # wire: compile the cell with the opt off and diff the collective
    # schedules.  The int8 all-gather/all-to-all traffic is classified as
    # gradient bytes by roofline.analysis; `wire_bytes_saved` is the
    # measured s8 gradient payload against the fp32 wire the same payload
    # costs uncompressed (32/bits ratio) — the per-cell proof that the
    # gradient traffic crosses the wire `bits`-wide.  `program_wire_delta`
    # is the whole-program ring-convention diff vs the other variant: an
    # honest, noisier number (the grouped-vmap bwd can shift GSPMD's
    # strategies elsewhere in the graph — see dist/README.md).  Cells that
    # never enable the opt skip the twin compile outright — pricing a wire
    # nobody asked for doubled every baseline sweep's train-cell time.
    grad_compress_cmp = None
    if shape.kind == "train" and "grad_compress" in opts:
        bits = 8
        alt_opts = set(opts) - {"grad_compress"}
        alt_rules, alt_rt = _make_runtime(arch, mesh, alt_opts)
        alt = _lower_compile(arch, shape, mesh, alt_rules, alt_rt, alt_opts)
        comp_info, base_info = full, alt
        grad_wire = comp_info["collectives"]["gradient_wire_bytes"]
        fp32_equiv = grad_wire * (32 // bits)
        grad_compress_cmp = {
            "enabled": True,
            "bits": bits,
            "scale_axis": "column" if "grad_compress_column" in opts else "tensor",
            "gradient_wire_bytes": grad_wire,
            "fp32_equivalent_bytes": fp32_equiv,
            "wire_bytes_saved": fp32_equiv - grad_wire,
            "baseline_program_wire": wire_bytes(base_info["collectives"]),
            "compressed_program_wire": wire_bytes(comp_info["collectives"]),
            "program_wire_delta": wire_bytes(base_info["collectives"])
            - wire_bytes(comp_info["collectives"]),
            "baseline_f32_allreduce_bytes": base_info["collectives"]["bytes_by_kind"]["all-reduce"],
            "compressed_f32_allreduce_bytes": comp_info["collectives"]["bytes_by_kind"]["all-reduce"],
        }
        record["grad_compress"] = grad_compress_cmp

    record.update(
        lower_s=full["lower_s"],
        compile_s=full["compile_s"],
        memory_analysis=full["memory_analysis"],
        raw_cost=full["cost"],
        raw_collectives=full["collectives"],
        hlo_bytes=full["hlo_bytes"],
        params_total=full["counts"]["total"],
        params_active=full["counts"]["active"],
    )

    # 2) roofline costing via marginal-layer extrapolation (single-pod table)
    if costing:
        base_arch, variants, sigs = _costing_variants(arch)
        base_info = _lower_compile(base_arch, shape, mesh, rules, rt, opts)
        variant_infos = {
            sig: _lower_compile(va, shape, mesh, rules, rt, opts) for sig, va in variants.items()
        }
        corrected = _combine(base_info, variant_infos, sigs, [s.count for s in arch.stacks])
        record["cost"] = corrected["cost"]
        record["collectives"] = corrected["collectives"]
        record["costing"] = {
            "method": "marginal-layer extrapolation (unrolled 1 vs 2 layer variants)",
            "base_cost": base_info["cost"],
            "n_variants": len(variant_infos),
        }
    else:
        record["cost"] = full["cost"]
        record["collectives"] = {
            "total_bytes": full["collectives"]["total_bytes"],
            "bytes_by_kind": full["collectives"]["bytes_by_kind"],
        }
    if grad_compress_cmp is not None:
        record["collectives"]["wire_bytes_saved"] = grad_compress_cmp["wire_bytes_saved"]
        record["collectives"]["gradient_wire_bytes"] = full["collectives"]["gradient_wire_bytes"]

    if shape.kind == "train":
        mf = model_flops(record["params_active"], shape.global_batch * shape.seq_len, "train")
    elif shape.kind == "prefill":
        mf = model_flops(record["params_active"], shape.global_batch * shape.seq_len, "fwd")
    else:
        mf = model_flops(record["params_active"], shape.global_batch, "fwd")

    terms = roofline_terms(
        flops_per_device=record["cost"]["flops"],
        bytes_per_device=record["cost"]["bytes accessed"],
        collective_bytes_per_device=record["collectives"]["total_bytes"],
        n_chips=mesh.size,
    )
    record["roofline"] = terms
    record["model_flops"] = mf
    flops_dev = record["cost"]["flops"]
    record["useful_flops_ratio"] = (mf / mesh.size) / flops_dev if flops_dev else None

    os.makedirs(os.path.join(out_dir, tag), exist_ok=True)
    fn = os.path.join(out_dir, tag, f"{arch_name}__{shape_name}__{record['mesh']}.json")
    with open(fn, "w") as f:
        json.dump(record, f, indent=1)
    # NB: no bare ternary around the whole f-string here — `f"..." if x else
    # "[ok]"` binds the conditional to the entire print argument and drops the
    # arch/shape/compile info whenever useful_flops_ratio is None.
    useful = record["useful_flops_ratio"]
    line = (
        f"[ok] {arch_name:24s} {shape_name:12s} {record['mesh']:8s} "
        f"compile={record['compile_s']}s dominant={terms['dominant']} "
        f"bound={terms['bound_s']:.4f}s"
    )
    if useful is not None:
        line += f" useful={useful:.3f}"
    if grad_compress_cmp is not None:
        line += f" wire_saved={grad_compress_cmp['wire_bytes_saved']:.3g}B"
    print(line)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--opt", action="append", default=[], help="hillclimb toggles")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-costing", action="store_true", help="compile-only (skip roofline variants)")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.configs import ARCH_NAMES

    cells = []
    archs = ARCH_NAMES if (args.all or args.arch is None) else [args.arch]
    for a in archs:
        arch = get_arch(a)
        shapes = applicable_shapes(arch) if (args.all or args.shape is None) else [args.shape]
        for s in shapes:
            meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
            for m in meshes:
                cells.append((a, s, m))

    failures = []
    for a, s, m in cells:
        try:
            # roofline costing on the single-pod mesh only (SRoofline is
            # single-pod); the multi-pod pass is the compile proof.
            run_cell(a, s, m, set(args.opt), args.out, args.tag, costing=(not m) and not args.no_costing)
        except Exception:
            failures.append((a, s, "multi" if m else "single"))
            print(f"[FAIL] {a} {s} {'multi' if m else 'single'}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print(f"all {len(cells)} cells passed")


if __name__ == "__main__":
    main()
