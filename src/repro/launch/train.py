"""Production training driver.

    python -m repro.launch.train --arch qwen3-0.6b --steps 100 \
        --seq 512 --global-batch 8 --mesh 1x1 \
        --ckpt-dir /tmp/run0 --ckpt-every 20 \
        --compress int8 [--fail-at 37] [--resume]

One entry point for the debug mesh (CPU), the single-pod 16x16 and the
multi-pod 2x16x16 production meshes (--mesh accepts "DxM" or "PxDxM").
Fault tolerance: periodic checkpoints, restart-from-latest (elastic: the
restore re-places leaves under whatever mesh the job came back with),
straggler monitoring, and optional injected failures to drill the path.
Distributed-optimization: gradient compression (int8 + error feedback or
top-k) before the optimizer; bf16 Adam moments for >=100B models.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import numpy as np


def parse_mesh(spec: str):
    import jax

    dims = tuple(int(x) for x in spec.lower().split("x"))
    if len(dims) == 2:
        return jax.make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return jax.make_mesh(dims, ("pod", "data", "model"))
    raise SystemExit(f"bad --mesh {spec!r} (want DxM or PxDxM)")


def build_state(cfg, opt_cfg, mesh, rng_seed: int = 0):
    import jax
    import jax.numpy as jnp
    from repro.distributed import sharding as shard_rules
    from repro.launch import steps as steps_mod
    from repro.models import transformer
    from repro.train import optim

    st_spec = steps_mod.state_specs(cfg, opt_cfg)
    st_shard = steps_mod.state_shardings(cfg, mesh, st_spec)

    @functools.partial(jax.jit, out_shardings=st_shard)
    def init(key):
        params = transformer.init_params(cfg, key)
        return {"params": params,
                "opt": optim.init_opt_state(params, opt_cfg)}

    with mesh:
        state = init(jax.random.PRNGKey(rng_seed))
    return state, st_spec, st_shard


def run_zkdl_train(cfg, args) -> int:
    """Prove-while-train for provable integer-SGD families: one
    aggregated proof per --prove-window steps, over the family's layer
    graph (uniform or a heterogeneous pyramid via --widths).

        python -m repro.launch.train --arch fcnn-zkdl-16l \
            --layers 2 --d-model 8 --global-batch 4 --steps 8 \
            --prove-window 4 [--widths 16,8,4,2] [--no-verify]

    Without overrides this runs the paper-scale 16x4096 network -- the
    same code path, just slow on a CPU substrate.

    With ``--proof-dir`` the resident warm prover service
    (`repro.launch.serve.ProverService`) takes over: setup AOT-compiles
    every prover executable (so the first window proves at steady-state
    speed), training never blocks on proving, and each window's proof
    streams to ``proof_NNNNNN.bin`` beside a serialized ``vk.bin``."""
    import numpy as np
    from repro.core import quantfc
    from repro.core.pipeline import compile as zk_compile
    from repro.launch import steps as steps_mod

    if args.widths:
        widths = tuple(int(w) for w in args.widths.split(","))
    else:
        layers = args.layers or cfg.n_layers
        width = args.d_model or cfg.d_model
        widths = (width,) * (layers + 1)
    window = max(1, args.prove_window)
    zk_cfg = steps_mod.build_proof_pipeline_config(
        cfg, batch=args.global_batch, n_steps=window, widths=widths)
    qc = quantfc.QuantConfig(q_bits=zk_cfg.q_bits, r_bits=zk_cfg.r_bits)
    shape = ("x".join(str(w) for w in widths) if len(set(widths)) > 1
             else f"{zk_cfg.n_layers} layers x {widths[0]} wide")
    print(f"[train] zkdl {cfg.family}: {shape}, "
          f"batch {args.global_batch}, aggregating {window} step(s)/proof",
          flush=True)
    import jax
    devs = jax.devices()
    print(f"[train] proving on {devs[0].platform} ({devs[0].device_kind}), "
          f"{len(devs)} device(s)", flush=True)

    service = None
    if args.proof_dir:
        from repro.launch.serve import ProverService
        service = ProverService(zk_cfg.graph, qc, n_steps=zk_cfg.n_steps,
                                out_dir=args.proof_dir,
                                verify=not args.no_verify)
        service.start(warm=True)
        pk, vk = service.pk, service.vk
        print(f"[train] prover service warm in {service.warm_seconds:.3f}s "
              f"(exec cache: {service.warm_stats}); streaming proofs to "
              f"{args.proof_dir}", flush=True)
    else:
        # one-time setup over the registered graph: the pk drives every
        # window's session; the vk alone (serializable, a few hundred
        # bytes) is what a remote verifier would hold
        pk, vk = zk_compile(zk_cfg.graph, qc, n_steps=zk_cfg.n_steps)
    rng = np.random.default_rng(0)
    ws = quantfc.init_weights(rng, widths, qc)
    data_x = rng.uniform(-1, 1, (args.global_batch * 8, widths[0]))
    data_y = rng.uniform(-1, 1, (args.global_batch * 8, widths[-1]))

    def on_proof(step, proof, dt):
        print(f"[train] step {step}: aggregated proof over "
              f"{proof.n_steps} steps, {proof.size_bytes() / 1024:.1f} kB "
              f"in {dt:.1f}s ({dt / proof.n_steps:.1f}s/step, "
              f"verified={not args.no_verify})", flush=True)

    hook = None
    if service is None:
        hook = steps_mod.ZkdlProveHook(pk, rng, verify=not args.no_verify,
                                       on_proof=on_proof)
    step_fn = steps_mod.build_zkdl_step(zk_cfg)
    for step in range(args.steps):
        lo = (step * args.global_batch) % data_x.shape[0]
        batch = {
            "x": quantfc.quantize(data_x[lo:lo + args.global_batch], qc),
            "y": quantfc.quantize(data_y[lo:lo + args.global_batch], qc),
        }
        t0 = time.perf_counter()
        ws, wit = step_fn(ws, batch)
        step_s = time.perf_counter() - t0          # training only; proving
        if service is not None:
            service.submit(wit)                    # non-blocking
        else:
            hook.observe(step, wit)                # logged per window
        if step % args.log_every == 0:
            print(f"[train] step {step} {step_s:.2f}s", flush=True)
    if service is not None:
        service.close()
        for window, path, n_bytes, secs in service.proofs:
            print(f"[train] window {window}: {n_bytes} B -> {path} "
                  f"({secs:.2f}s, verified={not args.no_verify})",
                  flush=True)
        n_proofs, pending = service.n_proofs, args.steps % window
    else:
        n_proofs, pending = len(hook.proofs), hook.n_pending
    print(f"[train] done: {args.steps} steps, {n_proofs} "
          f"aggregated proofs, {pending} step(s) pending "
          f"(next window)", flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--layers", type=int, default=0,
                    help="override n_layers (reduced runs)")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (drills restart)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--prove", action="store_true",
                    help="require prove-while-train (families without a "
                         "registered proof graph fail loudly)")
    ap.add_argument("--prove-window", type=int, default=4,
                    help="provable families: steps per aggregated proof")
    ap.add_argument("--widths", default=None,
                    help="provable families: heterogeneous shape table "
                         "d_0..d_L, e.g. 784,512,256,128,10")
    ap.add_argument("--no-verify", action="store_true",
                    help="provable families: skip verifying emitted proofs")
    ap.add_argument("--proof-dir", default=None,
                    help="provable families: run the resident warm prover "
                         "service and stream proof_NNNNNN.bin + vk.bin "
                         "into this directory (training never blocks)")
    args = ap.parse_args(argv)

    from repro.util import enable_compilation_cache
    enable_compilation_cache()
    from repro.configs.registry import get_config
    from repro.core.pipeline.graph import PROOF_GRAPH_BUILDERS
    arch_cfg = get_config(args.arch)
    if arch_cfg.family in PROOF_GRAPH_BUILDERS:
        return run_zkdl_train(arch_cfg, args)
    if args.prove:
        # one registry lookup; raises "no proof graph registered for
        # family ..." with the list of provable families
        from repro.core.pipeline.graph import proof_graph_for_family
        try:
            proof_graph_for_family(arch_cfg.family)
        except LookupError as exc:
            raise SystemExit(f"--prove: {exc}") from None
    import jax
    from repro.data import pipeline
    from repro.distributed import hints
    from repro.distributed import sharding as shard_rules
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import batch_axes
    from repro.launch.specs import train_batch_specs
    from repro.train import compression, optim, resilience

    cfg = get_config(args.arch)
    overrides = {}
    if args.layers:
        overrides["n_layers"] = args.layers
        if cfg.family == "encdec":
            overrides.update(enc_layers=args.layers, dec_layers=args.layers)
    if args.d_model:
        overrides["d_model"] = args.d_model
        overrides["head_dim"] = 0
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    mesh = parse_mesh(args.mesh)
    opt_cfg = steps_mod.default_opt_cfg(cfg)
    comp_cfg = compression.CompressionConfig(mode=args.compress)

    bax = batch_axes(mesh)
    sizes = {"batch": 1, "model": mesh.shape.get("model", 1)}
    for a in bax:
        sizes["batch"] *= mesh.shape[a]
    hints.set_axes(bax, "model" if "model" in mesh.axis_names else None,
                   sizes, mesh=mesh)

    # --- data + step -------------------------------------------------------
    source = pipeline.make_source(cfg, args.seq, args.global_batch)
    base_step = steps_mod.build_train_step(cfg, opt_cfg)

    def train_step(state, batch):
        import jax as _jax
        from repro.models import transformer as _t

        def loss_grads(p):
            return _t.loss_fn(cfg, p, batch)

        loss, grads = _jax.value_and_grad(loss_grads)(state["params"])
        grads, new_res = compression.compress_grads(
            comp_cfg, grads, state["residual"])
        new_params, new_opt, metrics = optim.apply_updates(
            opt_cfg, state["params"], grads, state["opt"])
        metrics["loss"] = loss
        return ({"params": new_params, "opt": new_opt,
                 "residual": new_res}, metrics)

    state, st_spec, st_shard = build_state(cfg, opt_cfg, mesh)
    state["residual"] = compression.init_residuals(state["params"]) \
        if comp_cfg.mode != "none" else {}
    res_shard = jax.tree.map(lambda _: shard_rules.replicated(mesh),
                             state["residual"])
    if comp_cfg.mode != "none":
        res_shard = shard_rules.param_shardings(cfg, mesh, state["residual"])
    full_shard = dict(st_shard, residual=res_shard)
    b_shard = shard_rules.batch_shardings(
        cfg, mesh, train_batch_specs(cfg, args.seq, args.global_batch))
    jitted = jax.jit(train_step, in_shardings=(full_shard, b_shard),
                     out_shardings=(full_shard, None), donate_argnums=(0,))

    policy = (resilience.CheckpointPolicy(args.ckpt_dir, args.ckpt_every)
              if args.ckpt_dir else None)
    injector = resilience.FailureInjector(args.fail_at)
    monitor = resilience.StragglerMonitor()

    def loop(st, start):
        nonlocal state
        if st is not None:
            state = st
        step = start
        while step < args.steps:
            t0 = time.perf_counter()
            injector.check(step)
            batch = source.batch(step)
            with mesh:
                state, metrics = jitted(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            monitor.observe(step, dt, lambda s, d: print(
                f"[train] straggler at step {s}: {d:.2f}s", flush=True))
            if step % args.log_every == 0:
                print(f"[train] step {step} loss {loss:.4f} {dt:.2f}s",
                      flush=True)
            if policy:
                policy.maybe_save(step, state)
            step += 1
        return state

    if policy:
        template = dict(st_spec, residual=state["residual"])
        state = resilience.run_resilient(loop, template, policy,
                                         shardings=full_shard)
    else:
        state = loop(None, 0)
    print(f"[train] done: {args.steps} steps, "
          f"straggler events: {len(monitor.events)}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
