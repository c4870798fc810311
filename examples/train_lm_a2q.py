"""End-to-end driver: train the full smollm-135m config (135M params, A2Q
hidden layers targeting 16-bit accumulators) for a few hundred steps on the
synthetic token stream, with checkpointing and resume.

    PYTHONPATH=src python examples/train_lm_a2q.py --steps 300
    PYTHONPATH=src python examples/train_lm_a2q.py --steps 300 --scale 0.25  # faster CPU run

The same entrypoint on a TPU fleet builds the production mesh (this is just
``launch/train.py`` pre-configured); on CPU one step of the full 135M model is
slow, so ``--scale`` optionally narrows the network (same depth/structure).
After training, verifies the A2Q invariant over every layer: integer-weight
l1 norms within the Eq. 15 budget for P=16.

Multi-device gradient compression (A2Q's accumulator argument applied to the
cross-device wire): on a mesh, put the data-parallel gradient all-reduce on
an int8 wire with error feedback by giving the Runtime a GradCompressConfig
and carrying the residual pair in the train state::

    from repro.dist.collectives import GradCompressConfig, resolve_grad_compress
    from repro.dist.sharding import ShardingRules, make_mesh, param_specs
    from repro.train.state import init_grad_err

    mesh  = make_mesh((8,), ("data",))
    rules = ShardingRules.default(mesh, arch)
    gc    = GradCompressConfig(bits=8, scale_axis="column")   # A2Q+-style scales
    rt    = Runtime(mesh=mesh, rules=rules, grad_compress=gc)
    step_fn = build_train_step(arch, opt, rt, lr_schedule=sched)

    pspecs = param_specs(jax.eval_shape(lambda: init_lm(key, arch)), mesh, rules)
    axis   = resolve_grad_compress(gc, mesh).axis
    state["grad_err"] = init_grad_err(params, mesh.shape[axis], pspecs=pspecs, axis=axis)

(or just pass ``--grad-compress-bits 8`` to ``repro.launch.train``).  The
20-step parity test in tests/test_sharding.py shows the compressed run
tracking fp32 within ~0.05 loss; ``launch/dryrun.py`` records the measured
wire-byte savings per train cell.
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.configs.base import AttnConfig, StackConfig
from repro.core.a2q import a2q_int_weights
from repro.core.bounds import l1_budget
from repro.data.synthetic import TokenStream
from repro.models import Runtime, init_lm
from repro.models.steps import build_train_step
from repro.nn.module import unbox
from repro.optim.optimizers import adamw
from repro.optim.schedules import cosine_with_warmup
from repro.train.trainer import Trainer


def scaled_smollm(scale: float):
    arch = get_arch("smollm-135m")
    if scale >= 1.0:
        return arch
    s = arch.stacks[0]
    heads = max(int(s.attn.heads * scale) // 3 * 3, 3)  # keep kv ratio 3:1
    a = dataclasses.replace(s.attn, heads=heads, kv_heads=heads // 3)
    return dataclasses.replace(
        arch,
        d_model=heads * s.attn.head_dim,
        vocab=max(int(arch.vocab * scale), 1024),
        stacks=(dataclasses.replace(s, attn=a, d_ff=max(int(s.d_ff * scale) // 8 * 8, 64)),),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--ckpt-dir", default="/tmp/a2q_lm_ckpt")
    args = ap.parse_args()

    arch = scaled_smollm(args.scale)
    n_params_est = arch.n_layers * (4 * arch.d_model**2 + 3 * arch.d_model * arch.stacks[0].d_ff)
    print(f"arch: {arch.name} x{args.scale} d={arch.d_model} L={arch.n_layers} "
          f"(~{(n_params_est + arch.vocab*arch.d_model)/1e6:.0f}M params), "
          f"A2Q P={arch.quant.acc_bits}")

    params = unbox(init_lm(jax.random.PRNGKey(0), arch))
    opt = adamw(weight_decay=1e-5)
    state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    sched = cosine_with_warmup(3e-4, warmup=args.steps // 10, total=args.steps)
    step_fn = build_train_step(arch, opt, Runtime(), lr_schedule=sched)
    stream = TokenStream(vocab=arch.vocab, seq_len=args.seq, global_batch=args.batch)

    trainer = Trainer(step_fn, stream.batch, ckpt_dir=args.ckpt_dir, ckpt_every=100, log_every=20)
    state, start = trainer.maybe_restore(state)
    res = trainer.run(state, args.steps, start_step=start)
    print(f"loss: {res.history[0]['loss']:.3f} -> {res.history[-1]['loss']:.3f}")

    # verify the guarantee over the trained model
    q = arch.quant
    budget = l1_budget(q.acc_bits, q.act_bits, True)
    worst = 0.0
    n_layers = 0

    def walk(node):
        nonlocal worst, n_layers
        if isinstance(node, dict):
            if "v" in node and "t" in node and node["v"].ndim >= 2:
                v, t, d = node["v"], node["t"], node["d"]
                lead = v.ndim - 2
                fn = lambda vv, tt, dd: a2q_int_weights(
                    {"v": vv, "t": tt, "d": dd}, q.weight_bits, q.acc_bits, q.act_bits, True
                )[0]
                for _ in range(lead):
                    fn = jax.vmap(fn)
                qi = np.asarray(fn(v, t, d))
                l1 = np.abs(qi).sum(axis=-2)
                worst = max(worst, float(l1.max()))
                n_layers += 1
            else:
                for vv in node.values():
                    walk(vv)

    walk(res.state["params"])
    ok = worst <= budget + 1e-6
    print(f"A2Q invariant over {n_layers} trained layers: worst |w|_1 = {worst:.2f} "
          f"<= budget {budget:.2f}: {'OK' if ok else 'VIOLATED'}")
    assert ok


if __name__ == "__main__":
    main()
