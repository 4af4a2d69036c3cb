"""End-to-end FL training driver (runnable on CPU with reduced configs).

Runs real FL rounds: per-pod local gradients -> torrent dissemination ->
masked FedAvg -> AdamW, with round-boundary checkpointing and restart
(--resume picks up at the latest checkpoint, the paper's §III-E
rejoin-at-round-boundary semantics).

``--drop-pod`` is a real recovery drill, not just a mask: at
``--drop-at`` (default steps/2) the run checkpoints, re-meshes from P to
P-1 pods (``ElasticFLStep`` rebuilds the mesh and the torrent ring
schedule for the shrunken collective), reloads the checkpoint, and
continues — asserting loss continuity across the re-mesh.  Params and
optimizer state carry over: a drop shrinks the swarm, never resets
training.

``--join-pod N`` is the symmetric growth drill (§III-E elastic P): at
``--join-at`` (default steps/2) N fresh pods join, the run checkpoints,
re-meshes from P to P+N over the enlarged device set
(``make_pod_mesh`` with the larger pod count), re-places the carried
params/optimizer state, and continues — loss continuity asserted the
same way.  A join widens the collective, never resets training.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --reduced --steps 200 --batch 8 --seq 64 --ckpt /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --pods 4 --drop-pod 2 \
        --reduced --steps 40 --batch 8 --seq 32
    PYTHONPATH=src python -m repro.launch.train --pods 3 --join-pod 1 \
        --reduced --steps 40 --batch 8 --seq 32
"""
from __future__ import annotations

import argparse
import math
import os
import time
from dataclasses import dataclass, field

import numpy as np


def synthetic_batch(rng: np.random.Generator, n_pods: int, b_local: int,
                    seq: int, vocab: int, *, frames: int = 0):
    """Deterministic LM stream: next-token-predictable structured data."""
    import jax.numpy as jnp
    if frames:
        x = rng.normal(size=(n_pods, b_local, seq, frames)).astype(
            np.float32)
        y = rng.integers(0, vocab, size=(n_pods, b_local, seq))
        return {"inputs": jnp.asarray(x), "labels": jnp.asarray(y)}
    base = rng.integers(0, vocab, size=(n_pods, b_local, 1))
    step = rng.integers(1, 7, size=(n_pods, b_local, 1))
    seqs = (base + step * np.arange(seq + 1)) % vocab
    return {"inputs": jnp.asarray(seqs[..., :-1], jnp.int32),
            "labels": jnp.asarray(seqs[..., 1:], jnp.int32)}


@dataclass
class TrainRun:
    """What :func:`train` leaves behind: the final state and, per
    executed step, its loss and host wall seconds (each step ends in a
    blocking read of its loss, so a step's seconds cover its device
    work)."""

    params: dict
    opt: object
    mesh: object = None            # mesh of the last step (None: no mesh)
    losses: list = field(default_factory=list)
    step_s: list = field(default_factory=list)


def train(cfg, *, steps: int = 100, batch: int = 8, seq: int = 64,
          lr: float = 3e-3, pods: int = 1, ckpt: str = "",
          ckpt_every: int = 50, drop_pod: int = -1, drop_at: int = -1,
          join_pod: int = 0, join_at: int = -1, log_every: int = 10,
          **step_kw) -> TrainRun:
    """FL training of ``cfg`` on this host's devices (see the module
    docstring for the drills).  ``step_kw`` goes to the FL step
    (:func:`repro.dist.fl_step.make_fl_train_step`)."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import latest_round, load_checkpoint, \
        save_checkpoint
    from repro.dist.fl_step import ElasticFLStep
    from repro.launch.mesh import make_host_mesh, make_pod_mesh
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.optim.schedules import linear_warmup_cosine

    n_dev = jax.device_count()
    n_pods = pods if pods > 1 else 1
    peak = n_pods + max(join_pod, 0)
    if peak > n_dev:
        raise SystemExit(f"--pods {pods} --join-pod "
                         f"{max(join_pod, 0)} needs >= {peak} XLA "
                         f"devices (have {n_dev}); set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    dpp = max(n_dev // peak, 1)        # data-parallel devices per pod

    def mesh_factory(p: int):
        if p == 1:
            return make_host_mesh((n_dev, 1), ("data", "model"))
        return make_pod_mesh(p, data=dpp)

    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    start = 0
    active_pods = n_pods
    if ckpt:
        r = latest_round(ckpt)
        if r is not None:
            (params, opt), meta = load_checkpoint(ckpt, r, (params, opt))
            start = r + 1
            # A checkpoint written after a drop records the shrunken
            # collective; resuming must not silently re-expand it.
            active_pods = int(meta.get("pods", n_pods))
            print(f"resumed from round {r} ({active_pods} pods)",
                  flush=True)

    step_fn = ElasticFLStep(
        cfg, lr_schedule=linear_warmup_cosine(lr, 10, max(steps, 20)),
        mesh_factory=mesh_factory, **step_kw)
    rng = np.random.default_rng(0)
    b_local = max(batch // n_pods, 1)
    frames = cfg.d_model if not cfg.has_embedding else 0

    drop_at = drop_at if drop_at >= 0 else steps // 2
    join_at = join_at if join_at >= 0 else steps // 2
    run = TrainRun(params=params, opt=opt)
    prev_loss = None
    check_continuity = False
    t0 = time.time()
    for it in range(start, steps):
        if (drop_pod >= 0 and it == drop_at and active_pods > 1):
            # §III-E recovery drill: durable state at the boundary,
            # shrink the collective, rebuild mesh + ring, continue.
            if ckpt:
                save_checkpoint(ckpt, it - 1, (params, opt),
                                meta={"arch": cfg.name,
                                      "pods": active_pods - 1})
                (params, opt), _ = load_checkpoint(ckpt, it - 1,
                                                   (params, opt))
            active_pods -= 1
            check_continuity = True
            print(f"step {it:5d}  pod {drop_pod % n_pods} dropped: "
                  f"re-meshing {active_pods + 1} -> {active_pods} pods",
                  flush=True)
        if (join_pod > 0 and it == join_at
                and active_pods + join_pod <= peak):
            # §III-E growth drill, the drop's symmetric twin: durable
            # state at the boundary, widen the collective, rebuild
            # mesh + ring over the enlarged device set, continue.
            if ckpt:
                save_checkpoint(ckpt, it - 1, (params, opt),
                                meta={"arch": cfg.name,
                                      "pods": active_pods + join_pod})
                (params, opt), _ = load_checkpoint(ckpt, it - 1,
                                                   (params, opt))
            active_pods += join_pod
            check_continuity = True
            print(f"step {it:5d}  {join_pod} pod(s) joined: "
                  f"re-meshing {active_pods - join_pod} -> "
                  f"{active_pods} pods", flush=True)
        t_step = time.perf_counter()
        data = synthetic_batch(rng, active_pods, b_local, seq, cfg.vocab,
                               frames=frames)
        params, opt, m = step_fn(params, opt, data,
                                 jnp.ones((active_pods,)),
                                 jnp.ones((active_pods,)))
        loss = float(m["loss"])
        run.losses.append(loss)
        run.step_s.append(time.perf_counter() - t_step)
        if check_continuity:
            # Continuity across the re-mesh: same params, resized
            # collective — anything beyond noise means recovery broke.
            # A re-mesh on the first executed step has no pre-re-mesh
            # loss to compare against; skip cleanly (disarm) rather
            # than grading two post-re-mesh losses next step.
            if prev_loss is not None:
                if not math.isfinite(loss) or loss > 3.0 * prev_loss + 0.5:
                    raise RuntimeError(
                        f"loss continuity broken across re-mesh: "
                        f"{prev_loss:.4f} -> {loss:.4f}")
                print(f"step {it:5d}  re-mesh continuity ok "
                      f"({prev_loss:.4f} -> {loss:.4f})", flush=True)
            check_continuity = False
        prev_loss = loss
        if it % log_every == 0 or it == steps - 1:
            print(f"step {it:5d}  loss {loss:.4f}  "
                  f"lr {float(m['lr']):.2e}  pods {active_pods}  "
                  f"({time.time() - t0:.1f}s)", flush=True)
        if ckpt and (it + 1) % ckpt_every == 0:
            save_checkpoint(ckpt, it, (params, opt),
                            meta={"arch": cfg.name, "pods": active_pods})
    if ckpt:
        save_checkpoint(ckpt, steps - 1, (params, opt),
                        meta={"arch": cfg.name, "pods": active_pods,
                              "final": True})
    run.params, run.opt = params, opt
    run.mesh = step_fn.step_for(active_pods)[0]
    return run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--drop-pod", type=int, default=-1,
                    help="mid-run pod failure: checkpoint, re-mesh "
                         "P->P-1, continue (loss continuity asserted)")
    ap.add_argument("--drop-at", type=int, default=-1,
                    help="step of the pod failure (default steps/2)")
    ap.add_argument("--join-pod", type=int, default=0,
                    help="mid-run pod growth: N pods join, checkpoint, "
                         "re-mesh P->P+N over the enlarged device set, "
                         "continue (loss continuity asserted)")
    ap.add_argument("--join-at", type=int, default=-1,
                    help="step of the pod join (default steps/2)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    # Multi-pod runs need one XLA device per pod — including the pods
    # that will only exist after a --join-pod re-mesh; on a plain CPU
    # host fake them BEFORE the backend initializes (no-op if the
    # operator already set a device count or real accelerators exist).
    peak_pods = args.pods + max(args.join_pod, 0)
    if peak_pods > 1 and ("xla_force_host_platform_device_count"
                          not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={peak_pods}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run = train(get_config(args.arch, reduced=args.reduced),
                steps=args.steps, batch=args.batch, seq=args.seq,
                lr=args.lr, pods=args.pods, ckpt=args.ckpt,
                ckpt_every=args.ckpt_every, drop_pod=args.drop_pod,
                drop_at=args.drop_at, join_pod=args.join_pod,
                join_at=args.join_at, log_every=args.log_every)
    final_loss = run.losses[-1]
    print(f"done: final loss {final_loss:.4f}", flush=True)
    return final_loss


if __name__ == "__main__":
    main()
