"""Plan-driven CNN training launcher.

    python -m repro.launch.train_cnn --smoke [--steps N] [--sharded] \
        [--ckpt-dir DIR] [--metrics-out PATH] [--check-loss]
    python -m repro.launch.train_cnn --model resnet50 --width 64 --res 224 \
        --classes 1000 --batch 128 --microbatches 1

The second line trains ResNet-50 v1.5 at its published widths
(``--width`` is the stem's and the first stage's width; the stages double
it); smaller ``--width`` and ``--res`` keep its 53-conv depth.

Every fprop/dgrad/wgrad in the run dispatches through a prewarmed
``ConvPlan`` (``repro.train.cnn`` over a ``ModelPlans``): plans are built
once for the microbatch geometry before step 0, the first step compiles,
and — under ``--strict`` (default) — the remaining steps run inside a
``resolution_guard`` that raises if any schedule resolution happens in
steady state.  ``--smoke`` is the CPU/CI path: the small 3-conv CNN on
step-indexed synthetic images with class structure, so the loss genuinely
descends (``--check-loss`` fails the run otherwise).  ``--sharded`` builds
mesh-sharded plan triples over the host's device ring instead
(``repro.shard.autodiff``).

The run records the ``repro.train.*`` metrics (step_s, grads_s, update_s,
plan_hit_rate, steps, examples, loss), streams every plan's (predicted,
measured) dispatch pair into the cost-model drift monitor, and can dump
both as one obs artifact (``--metrics-out``).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.data.pipeline import SyntheticImages
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.drift import default_monitor
from repro.obs.metrics import default_metrics
from repro.train import checkpoint as ckpt
from repro.train import cnn as tc
from repro.train.optimizer import AdamWConfig


def build_model(args):
    """(params, plans, graph) for the requested model/geometry — plans
    built for the *microbatch* batch size; ``graph`` is the layer graph
    (None: the relu chain of the plans' layers)."""
    from repro.core.autodiff import make_model_plans
    from repro.models import cnn as M
    mb = args.batch // args.microbatches
    devices = tuple(jax.devices()) if args.sharded else None
    key = jax.random.PRNGKey(args.seed)
    if args.model == "small":
        params = M.init_small_cnn(key, in_ch=args.channels,
                                  n_classes=args.classes, width=args.width)
        plans = M.small_cnn_plans(params, mb, args.res,
                                  policy=args.policy, devices=devices)
    elif args.model == "resnet50":
        scenes = M.resnet_scenes(
            mb, args.res, in_ch=args.channels, stem=args.width,
            widths=tuple(args.width * 2 ** i for i in range(4)))
        params = M.init_resnet(key, scenes, n_classes=args.classes)
        plans = make_model_plans(scenes, policy=args.policy, devices=devices)
        return params, plans, M.resnet_graph()
    else:
        scenes = M.vgg_style_scenes(
            mb, res=args.res, in_ch=args.channels,
            stages=((args.width, 1), (args.width * 2, 2),
                    (args.width * 4, 2)))
        params = M.init_cnn_from_scenes(key, scenes, n_classes=args.classes)
        plans = make_model_plans(scenes, policy=args.policy, devices=devices)
    return params, plans, None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="small",
                    choices=("small", "vgg", "resnet50"))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--res", type=int, default=8)
    ap.add_argument("--channels", type=int, default=3)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="analytic")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU/CI path (kept explicit for parity with "
                         "launch.train; the defaults above are smoke-sized)")
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-sharded plan triples over jax.devices()")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="",
                    help="dump metrics + drift snapshot as one obs artifact")
    ap.add_argument("--check-loss", action="store_true",
                    help="exit nonzero unless the loss decreased")
    ap.add_argument("--no-strict", dest="strict", action="store_false",
                    help="disable the steady-state zero-resolution guard")
    args = ap.parse_args()
    enable_compile_cache()
    if args.batch % args.microbatches:
        raise ValueError(f"--batch {args.batch} not divisible by "
                         f"--microbatches {args.microbatches}")

    m = default_metrics()
    params, plans, graph = build_model(args)
    ref_ops = plans.reference_ops
    if ref_ops:
        print(f"reference fallbacks: {ref_ops}")
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=2,
                          total_steps=max(args.steps, 1))
    buckets = tc.make_grad_buckets(params)
    step_fn = tc.build_cnn_train_step(plans, opt_cfg,
                                      n_microbatches=args.microbatches,
                                      buckets=buckets, graph=graph)
    jstep = tc.jit_train_step(step_fn)
    state = tc.init_train_state(params)
    data = SyntheticImages(args.batch, args.res, args.channels,
                           args.classes, seed=args.seed, noise=0.3)

    start = 0
    if args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            state, extra = ckpt.restore(args.ckpt_dir, last, state)
            start = extra["next_step"]
            print(f"resumed at step {start}")

    def run_step(i):
        batch = jax.tree.map(jnp.asarray, data.batch_at(i))
        t0 = time.perf_counter()
        new_state, metrics = tc.dispatch_step(jstep, state, batch)
        jax.block_until_ready(metrics["loss"])
        tc.observe_step(time.perf_counter() - t0, metrics["loss"],
                        args.batch, m)
        return new_state, metrics

    losses = []

    def after_step(i, metrics):
        losses.append(float(metrics["loss"]))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={losses[-1]:.4f} "
                  f"acc={float(metrics['accuracy']):.2f}")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, i + 1, state,
                      extra={"next_step": i + 1,
                             "loss": losses[-1]})
            ckpt.retain(args.ckpt_dir)

    # warmup step: compiles the fused step (plans were prewarmed at build)
    if start < args.steps:
        state, metrics = run_step(start)
        after_step(start, metrics)
    if args.strict:
        with tc.resolution_guard(m):
            for i in range(start + 1, args.steps):
                state, metrics = run_step(i)
                after_step(i, metrics)
    else:
        for i in range(start + 1, args.steps):
            state, metrics = run_step(i)
            after_step(i, metrics)

    # sharded triples build outside the registry — hit rate only means
    # something for the in-process plan path
    hit_rate = (tc.observe_plan_hit_rate(metrics=m)
                if not args.sharded else float("nan"))
    if start < args.steps:
        mb = args.batch // args.microbatches
        mb_batch = {k: v[:mb] for k, v in
                    jax.tree.map(jnp.asarray, data.batch_at(0)).items()}
        breakdown = tc.profile_step_breakdown(state, mb_batch, plans,
                                              opt_cfg, graph=graph,
                                              metrics=m)
        fed = tc.feed_drift_from_plans(plans)
        print(f"plan_hit_rate={hit_rate:.3f} "
              f"grads_s={breakdown['grads_s']:.4f} "
              f"update_s={breakdown['update_s']:.4f} drift_pairs={fed}")
    if args.metrics_out:
        path = m.dump(args.metrics_out,
                      extra={"drift": default_monitor().snapshot()})
        print(f"metrics -> {path}")
    if args.check_loss and losses:
        first, last = losses[0], losses[-1]
        if not last < first:
            raise SystemExit(
                f"loss did not decrease: step0 {first:.4f} -> "
                f"final {last:.4f}")
        print(f"loss decreased: {first:.4f} -> {last:.4f}")
    print("training complete")


if __name__ == "__main__":
    main()
