"""Training cells: A2Q quantization-aware training through the program's
jitted step (``repro.models.steps.build_train_step``) with the optimizer and
learning-rate schedule ``repro.launch.train.train`` uses.

Set-up builds one step object and its state, and drives it through its
first ``check_steps`` steps with the window's own call and feed; the window
then runs the same object on.  ``train_tok_s`` is the tokens of every step
completed in the window (each ends in ``block_until_ready``) over the
window's length.

On more than one device the step, its state and each batch are laid out
as ``repro.launch.train.train`` lays them out: the planned ``data x model``
mesh, ``ShardingRules.default``, the state by ``make_state_specs``, a batch
by the ``batch`` rule; the weights are made straight into their shardings.
The reference runs on the same mesh.  On one device there is no mesh.

Once the window has closed and the program's state is freed, the plain
float32 reference (``reference/train.py``) runs the same first steps from
the same weights and rows.  Compared, each by the worst leaf where a leaf is
involved: each step's loss; the norm of the first step's clipped gradient
as the optimizer was given it (read back from AdamW's first moment); the
norm of each parameter's change over the first steps.  Leaves whose
reference gradient is below a thousandth of the median leaf's are left out
of the two leaf comparisons: under AdamW they move by round-off alone.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import core
from bench.model import arch_from_config, make_params
from bench.traffic import tokens as feed


def _layout(arch, devices) -> tuple:
    """The mesh and sharding rules ``repro.launch.train.train`` builds over
    ``devices``, with the plan; ``(None, None, None)`` on one device."""
    if len(devices) == 1:
        return None, None, None
    from repro.dist.sharding import ShardingRules, make_mesh
    from repro.train.elastic import plan_mesh

    plan = plan_mesh(len(devices), model_divisors=[s.attn.heads for s in arch.stacks if s.attn])
    mesh = make_mesh(plan["shape"], plan["axes"], devices=devices)
    return mesh, ShardingRules.default(mesh, arch), dict(zip(plan["axes"], plan["shape"]))


def _shardings(arch, opt, mesh, rules, rows: int, seq: int) -> tuple:
    """Where the train state and a ``(rows, seq)`` batch live on ``mesh``."""
    import jax
    from jax.sharding import NamedSharding

    from repro.dist.sharding import resolve_pspec
    from repro.models.lm import init_lm
    from repro.train.state import make_state_specs, specs_to_shardings

    boxed = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), arch))
    state = specs_to_shardings(make_state_specs(boxed, opt, mesh, rules), mesh)
    return state, NamedSharding(mesh, resolve_pspec(("batch", None), (rows, seq), mesh, rules))


def _program_step(arch, cfg, mesh=None, rules=None):
    import jax

    from repro.models.lm import Runtime
    from repro.models.steps import build_train_step
    from repro.optim.optimizers import adamw
    from repro.optim.schedules import cosine_with_warmup

    t = cfg["train"]
    opt = adamw(b1=t["b1"], b2=t["b2"], eps=t["eps"], weight_decay=t["weight_decay"])
    sched = cosine_with_warmup(t["lr"], warmup=t["warmup"], total=t["total_steps"])
    fn = build_train_step(arch, opt, Runtime(mesh=mesh, rules=rules), lr_schedule=sched,
                          grad_clip=t["grad_clip"])
    return jax.jit(fn, donate_argnums=(0,)), opt


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])(
        [x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def _diff_norms(a, b, scale_a: float = 1.0) -> dict:
    """Per leaf, ``||scale_a * a - b||``, the leaves paired by their path."""
    import jax
    import jax.numpy as jnp

    fa = dict((jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_flatten_with_path(a)[0])
    fb = dict((jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_flatten_with_path(b)[0])
    keys = sorted(fb)
    norms = jax.jit(lambda xs, ys: [
        jnp.sqrt(jnp.sum(jnp.square(scale_a * x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(xs, ys)])([fa[k] for k in keys], [fb[k] for k in keys])
    return {k: float(n) for k, n in zip(keys, norms)}


def leaf_gaps(prog: dict, ref: dict, grad_ref: dict, difference: bool = False) -> dict:
    """Per leaf, ``|prog - ref| / max(ref, median ref)`` of two norms, or with
    ``difference`` the norm of the difference ``prog / max(ref, median ref)``,
    over the leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    gmed = float(np.median(list(grad_ref.values())))
    keep = [k for k in ref if grad_ref[k] >= 1e-3 * gmed]
    med = float(np.median([ref[k] for k in keep]))
    return {k: (prog[k] if difference else abs(prog[k] - ref[k])) / max(ref[k], med) for k in keep}


def _worst(gaps: dict, n: int = 5) -> list:
    return sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:n]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, w = ctx.cell.config, ctx.cell.workload
    arch = arch_from_config(cfg)
    rows, seq, n_check = int(w["batch"]), int(w["seq"]), int(w["check_steps"])
    mesh, rules, ctx.counters["mesh"] = _layout(arch, ctx.devices)
    step_fn, opt = ctx.overrides.get("train_step") or _program_step(arch, cfg, mesh, rules)
    state_at, batch_at = _shardings(arch, opt, mesh, rules, rows, seq) if mesh else (None, None)
    params_at = state_at["params"] if mesh else None
    t = time.perf_counter()
    params = jax.block_until_ready(make_params(cfg, ctx.seed, deployed=False, shardings=params_at))
    params0 = jax.device_get(params)  # on the host: the step's memory is the program's
    ctx.setup["weights_s"] = time.perf_counter() - t
    state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    if mesh:
        state = jax.device_put(state, state_at)

    def batch(i):
        b = feed.batch(ctx.seed, i, rows, seq, cfg["vocab_size"])
        # one device: the step takes the host arrays, its transfer inside the call
        return jax.device_put(b, batch_at) if mesh else b

    b1 = cfg["train"]["b1"]

    t = time.perf_counter()
    losses, grad_prog = [], None
    for i in range(n_check):
        state, m = step_fn(state, batch(i))
        losses.append(float(m["loss"]))
        if i == 0:  # AdamW's first moment after one step is (1 - b1) x the gradient it got
            grad_prog = {k: v / (1 - b1) for k, v in _leaf_norms(state["opt_state"]["m"]).items()}
            m_first = jax.device_get(state["opt_state"]["m"])
    change_prog = _diff_norms(state["params"], jax.device_put(params0, params_at))
    ctx.setup["first_steps_s"] = time.perf_counter() - t
    ctx.begin_window()

    t0 = time.perf_counter()
    trace_on, trace_dir, tw = ctx.trace, ctx.out_dir / "trace", [0.0, 0.0]
    trace_at = t0 + float(w.get("trace_after", 2.0))
    trace_end = trace_at + float(w.get("trace_seconds", 3.0))
    done, i = 0, n_check
    traced = ctx.counters.setdefault("steps", [])
    try:
        while True:
            now = time.perf_counter()
            if trace_on and not tw[0] and now >= trace_at:
                jax.profiler.start_trace(str(trace_dir))
                tw[0] = time.perf_counter()
            if tw[0] and not tw[1] and now >= trace_end:
                tw[1] = time.perf_counter()
                jax.profiler.stop_trace()
            if now - t0 >= ctx.seconds and not (tw[0] and not tw[1] and not traced):
                break  # a traced run's window holds at least one traced step
            if tw[0] and not tw[1]:
                s0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step"):
                    state, m = step_fn(state, batch(i))
                    jax.block_until_ready(m["loss"])
                traced.append({"t0": s0, "t1": time.perf_counter()})
            else:
                state, m = step_fn(state, batch(i))
                jax.block_until_ready(m["loss"])
            done += 1
            i += 1
        t1 = time.perf_counter()
    finally:
        if tw[0] and not tw[1]:
            tw[1] = time.perf_counter()
            jax.profiler.stop_trace()
    ctx.end_window()
    if tw[1]:
        ctx.trace_dir = trace_dir
    ctx.record.window = (t0, t1)
    ctx.record.trace_window = tuple(tw)
    ctx.counters.update(window_steps=done, rows=rows, seq=seq, tokens_per_step=rows * seq,
                        window_s=t1 - t0, last_loss=float(m["loss"]))
    e2e = {"train_tok_s": done * rows * seq / (t1 - t0)}
    ctx.read_memory()
    del state, m, params
    gc.collect()

    t = time.perf_counter()
    from bench.reference import train as ref

    rpb = int(w["reference_rows_per_block"])
    # the state is donated and each step's gradients dropped before the
    # next, so that no more than one state and one gradient are live
    rstep = jax.jit(lambda s, b: ref.step(s, b, cfg, rows_per_block=rpb), donate_argnums=(0,))
    with jax.default_matmul_precision("highest"):
        rs = ref.init_state(jax.device_put(params0, params_at))
        ref_losses, grad_ref = [], None
        for j in range(n_check):
            rs, l, g = rstep(rs, batch(j))
            ref_losses.append(float(l))
            if j == 0:
                grad_ref = _leaf_norms(g)
                grad_diff = _diff_norms(jax.device_put(m_first, params_at), g, 1.0 / (1 - b1))
                del m_first
            del g
        change_ref = _diff_norms(rs["params"], jax.device_put(params0, params_at))
    ctx.counters["reference_s"] = time.perf_counter() - t
    g_gaps = leaf_gaps(grad_prog, grad_ref, grad_ref)
    c_gaps = leaf_gaps(change_prog, change_ref, grad_ref)
    d_gaps = leaf_gaps(grad_diff, grad_ref, grad_ref, difference=True)
    numbers = {"loss_gap": max(abs(a - b) for a, b in zip(losses, ref_losses)),
               "grad_norm_gap": max(g_gaps.values()),
               "grad_norm_gap_median": float(np.median(list(g_gaps.values()))),
               "change_norm_gap": max(c_gaps.values()),
               "change_norm_gap_median": float(np.median(list(c_gaps.values()))),
               "grad_diff": max(d_gaps.values()),
               "grad_diff_median": float(np.median(list(d_gaps.values())))}
    ctx.counters.update(losses=losses, ref_losses=ref_losses, numbers=numbers,
                        grad_gap_worst=_worst(g_gaps), change_gap_worst=_worst(c_gaps),
                        grad_diff_worst=_worst(d_gaps),
                        leaves_compared=len(g_gaps), leaves=len(grad_ref))
    checks = [core.Check(k, numbers[k], lim) for k, lim in w["limits"].items()]
    checks.append(core.Check("window_compiles", float(ctx.window_compiles), 0.0))
    return {"e2e": e2e, "checks": checks, "attempted": done + n_check, "failed": 0}
