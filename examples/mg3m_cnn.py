"""Paper-faithful example: train a small CNN classifier whose every
convolution runs through MG3MConv, with the per-layer execution plans
(fprop + dgrad + wgrad, each through the multi-grained selector) built
once before training starts.

    PYTHONPATH=src python examples/mg3m_cnn.py --steps 30
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.models.cnn import (init_small_cnn, small_cnn_forward,
                              small_cnn_plans, small_cnn_reference)


def make_data(key, n, res=16):
    """Separable synthetic task: each image = noise + its class template."""
    kx, ky, kc = jax.random.split(key, 3)
    y = jax.random.randint(kc, (n,), 0, 10)
    templates = jax.random.normal(ky, (10, res, res, 3))
    x = 0.5 * jax.random.normal(kx, (n, res, res, 3)) + templates[y]
    return x, y


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--res", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-2)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    params = init_small_cnn(key)

    # Plan every layer ONCE, all three directions; training then never
    # re-runs schedule resolution.  The table shows what the selector
    # picked per layer and direction.
    plans = small_cnn_plans(params, args.batch, args.res)
    for name, triple in plans.items():
        print(f"{name}: fprop={triple.fprop.schedule} "
              f"dgrad={triple.dgrad.schedule or 'jnp-ref'} "
              f"wgrad={triple.wgrad.schedule or 'jnp-ref'} "
              f"for {triple.scene.describe()}")
    xs, ys = make_data(jax.random.PRNGKey(1), 512, args.res)

    def loss_fn(p, x, y):
        logits = small_cnn_forward(p, x, plans=plans)
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, y[:, None], 1).mean()

    # Adam via the framework optimizer (train/optimizer.py)
    from repro.train import optimizer as O
    opt_cfg = O.AdamWConfig(lr=args.lr, weight_decay=0.0, warmup_steps=2,
                            total_steps=args.steps)
    opt_state = O.init_opt_state(params)

    @jax.jit
    def step(p, ost, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        p, ost, _ = O.adamw_update(opt_cfg, p, g, ost)
        return p, ost, loss

    n = xs.shape[0]
    for i in range(args.steps):
        lo = (i * args.batch) % (n - args.batch)
        t0 = time.time()
        params, opt_state, loss = step(params, opt_state,
                                       xs[lo:lo + args.batch],
                                       ys[lo:lo + args.batch])
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss={float(loss):.4f} "
                  f"({(time.time()-t0)*1e3:.0f}ms)")

    # the planned forward against the plain reference, then accuracy
    got = small_cnn_forward(params, xs[:args.batch], plans=plans)
    want = small_cnn_reference(params, xs[:args.batch])
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"planned forward vs reference: max |err| = {err:.2e}")
    assert err < 1e-3
    logits = small_cnn_reference(params, xs[:256])
    acc = float((jnp.argmax(logits, -1) == ys[:256]).mean())
    print(f"train accuracy: {acc:.1%}")
    assert acc > 0.2, "should beat 10% chance comfortably"
    print("OK")


if __name__ == "__main__":
    main()
