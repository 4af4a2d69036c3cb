"""Pod-masked FL training step and the decode serving step.

``make_fl_train_step`` builds the round step the paper's system runs on
a multi-pod mesh (§III):

    1. every pod computes the gradient of ITS batch shard locally
       (vmap over the leading pod axis; within a pod the batch is
       data-parallel over the ``data`` mesh axis),
    2. the per-pod gradients are disseminated with the torrent ring
       (``torrent_fedavg`` — explicit block-wise ppermute schedule),
    3. the masked FedAvg aggregate drives ONE AdamW update, identical
       on every pod.

Fault tolerance is a mask, never a blocked collective: a straggler pod
(``active[p] == 0``) still participates in the fixed ring schedule, but
its contribution is multiplied by exactly 0.0 — its batch provably
cannot influence the result, and no peer waits on it beyond the
constant P-1 stages.  With full participation and equal weights the
step is bit-close to plain data-parallel SGD (the FedAvg of per-pod
mean gradients IS the global mean gradient).

``n_pods == 1`` folds the pod axis into the batch and runs plain DP
SGD — the degenerate ring (P-1 = 0 stages) with no collective.

``ElasticFLStep`` is the cross-round elastic form (§III-E): when the
active pod count changes between rounds (a pod drops, a client
rejoins), it rebuilds the mesh AND the ring schedule for the new P and
re-jits — cached per P, so oscillating P -> P-1 -> P pays the re-mesh
cost once per distinct pod count.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.dist.torrent import masked_weights, torrent_fedavg
from repro.models import decode_step, train_loss
from repro.models.layers import flash_attention_layers, merge_stats
from repro.optim import adamw_update
from repro.sharding.api import DEFAULT_RULES, axis_rules


def _microbatched_value_and_grad(loss_fn, params, inp, lab,
                                 microbatch: int):
    """((loss, stats), d loss / d params), accumulated over
    microbatches when enabled.

    ``loss_fn(params, inputs, labels) -> (loss, stats)``, ``stats`` the
    routing counts (``layers.merge_stats`` combines them); the local
    batch dim is split into ``b // microbatch`` scan steps so activation
    memory scales with the microbatch, not the batch.
    """
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    b = inp.shape[0]
    if microbatch <= 0 or b <= microbatch:
        return vg(params, inp, lab)
    if b % microbatch:
        raise ValueError(f"local batch {b} is not divisible by "
                         f"microbatch {microbatch}; the split would "
                         "silently fall back to full-batch memory")
    nmb = b // microbatch
    ib = inp.reshape((nmb, microbatch) + inp.shape[1:])
    lb = lab.reshape((nmb, microbatch) + lab.shape[1:])

    def one(carry, xy):
        (loss, stats), grads = vg(params, xy[0], xy[1])
        acc_l, acc_g = carry
        acc_g = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc_g, grads)
        return (acc_l + loss, acc_g), stats

    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params)
    (loss, grads), stats = jax.lax.scan(one, (jnp.zeros((), jnp.float32),
                                              zeros), (ib, lb))
    scale = 1.0 / nmb
    return ((loss * scale, merge_stats([stats])),
            jax.tree_util.tree_map(lambda g: g * scale, grads))


def make_fl_train_step(cfg, mesh, *, lr_schedule, n_pods: int,
                       rules=None, torrent_blocks: int = 4,
                       compress: bool = False, microbatch: int = 0,
                       ce_chunk: int = 512):
    """Returns step(params, opt, batch, weights, active) ->
    (params, opt, {"loss", "lr", ...}); an MoE model adds its routing
    counts (``layers.MOE_STATS``) summed over the step's layers.

    batch: {"inputs": (n_pods, B_local, T[, D]), "labels": (...)} —
    the leading axis is the pod (FL client) axis; weights/active are
    (n_pods,) FedAvg weights and the round's participation mask.
    """
    rules = dict(DEFAULT_RULES if rules is None else rules)
    has_pod_axis = (mesh is not None and "pod" in mesh.axis_names
                    and n_pods > 1)

    def step(params, opt, batch, weights, active):
        with axis_rules(rules, mesh):
            def loss_fn(p, x, y):
                return train_loss(cfg, p, x, y, ce_chunk=ce_chunk,
                                  with_stats=True)

            if n_pods <= 1:
                inp = batch["inputs"].reshape(
                    (-1,) + batch["inputs"].shape[2:])
                lab = batch["labels"].reshape(
                    (-1,) + batch["labels"].shape[2:])
                (loss, stats), agg = _microbatched_value_and_grad(
                    loss_fn, params, inp, lab, microbatch)
            else:
                def pod_grads(inp, lab):
                    return _microbatched_value_and_grad(
                        loss_fn, params, inp, lab, microbatch)

                (losses, stats), grads = jax.vmap(pod_grads)(
                    batch["inputs"], batch["labels"])
                stats = merge_stats([stats])
                if has_pod_axis:
                    # per-pod grads live on their pod (leading axis
                    # sharded); the ring is the only cross-pod traffic
                    grads = jax.tree_util.tree_map(
                        lambda g: jax.lax.with_sharding_constraint(
                            g, NamedSharding(mesh, P("pod"))), grads)
                agg = torrent_fedavg(
                    grads, weights, active,
                    mesh=mesh if has_pod_axis else None,
                    n_blocks=torrent_blocks, compress=compress)
                wn = masked_weights(weights, active)
                # select (don't multiply): a pod masked because it
                # diverged reports a NaN loss, and 0 * NaN == NaN
                loss = jnp.sum(jnp.where(
                    wn > 0, losses.astype(jnp.float32), 0.0) * wn)
            lr = lr_schedule(opt.step)
            new_params, new_opt = adamw_update(agg, opt, params, lr=lr)
            if n_pods > 1:
                # A round with zero active mass is a protocol no-op:
                # params, moments, and the step counter stay untouched
                # (zero grads would still apply weight decay and
                # advance the LR schedule).  Same zero-mass definition
                # as the aggregator's, so they cannot drift.
                has_mass = jnp.any(wn > 0)
                def pick(new, old):
                    return jnp.where(has_mass, new, old)
                new_params = jax.tree_util.tree_map(pick, new_params,
                                                    params)
                new_opt = jax.tree_util.tree_map(pick, new_opt, opt)
        return new_params, new_opt, {"loss": loss, "lr": lr, **stats}

    return step


class ElasticFLStep:
    """Elastic-P FL step: re-mesh + ring-schedule rebuild across rounds.

    ``mesh_factory(p)`` returns the mesh to train ``p`` active pods on
    (or None for the single-device path); it is consulted once per
    distinct pod count.  Each call dispatches on the batch's leading
    (pod) axis, so the caller just slices its batch to the surviving
    pods — e.g. with :func:`repro.dist.torrent.take_pods` — and the
    step re-meshes itself:

        step = ElasticFLStep(cfg, lr_schedule=sched, mesh_factory=mf)
        params, opt, m = step(params, opt, batch4, w4, a4)   # P=4 ring
        params, opt, m = step(params, opt, batch3, w3, a3)   # P=3 ring
        params, opt, m = step(params, opt, batch4, w4, a4)   # cached

    The first call at a new P pays one trace/compile (measured as
    ``remesh_ms`` in benchmarks/bench_session.py); revisited pod counts
    hit the cache.  Params/opt state carry across re-meshes unchanged —
    the §III-E recovery contract: a drop shrinks the collective, never
    resets training.

    Under an enabled ``repro.obs`` recorder an MoE model's step adds its
    routing counts to the counters ``moe.assignments`` (token-expert
    pairs its held experts computed, over all MoE layers) and
    ``moe.max_load`` (the busiest held expert's pairs over the mean,
    the worst layer); fetching them waits for the step.  Every model
    with attention adds ``attn.flash_layers`` (its attention layers that
    ``kernels.ops.flash_block`` sends to the causal flash kernels at the
    batch's sequence length, this backend and the step's mesh) and
    ``attn.layers`` (its attention layers) per step, known without
    waiting.
    """

    def __init__(self, cfg, *, lr_schedule, mesh_factory, **step_kw):
        self.cfg = cfg
        self.lr_schedule = lr_schedule
        self.mesh_factory = mesh_factory
        self.step_kw = dict(step_kw)
        self._cache: dict[int, tuple] = {}
        self._last_p: int | None = None

    def step_for(self, n_pods: int):
        """(mesh, jitted step) for ``n_pods`` active pods; cached."""
        if n_pods not in self._cache:
            mesh = self.mesh_factory(n_pods)
            step = make_fl_train_step(
                self.cfg, mesh, lr_schedule=self.lr_schedule,
                n_pods=n_pods, **self.step_kw)
            # Params and optimizer state are donated: the step replaces
            # them, so their buffers hold the new state in place.
            self._cache[n_pods] = (mesh, jax.jit(step,
                                                 donate_argnums=(0, 1)))
        return self._cache[n_pods]

    @property
    def pod_counts(self) -> list[int]:
        """Pod counts a step has been built for (re-mesh history)."""
        return sorted(self._cache)

    def __call__(self, params, opt, batch, weights, active):
        p = int(batch["inputs"].shape[0])
        mesh, jstep = self.step_for(p)
        if mesh is not None and p != self._last_p:
            # Carried state is committed to the PREVIOUS mesh's device
            # set; replicate it onto the new (possibly smaller) one so
            # the re-jitted step can re-shard it internally.
            sh = NamedSharding(mesh, P())
            params, opt = jax.device_put((params, opt), sh)
        self._last_p = p
        ctx = mesh if mesh is not None else contextlib.nullcontext()
        with ctx:
            out = jstep(params, opt, batch, jnp.asarray(weights),
                        jnp.asarray(active))
        rec = obs.get()
        if rec.enabled:
            flash, layers = flash_attention_layers(
                self.cfg, int(batch["inputs"].shape[2]),
                jax.default_backend(),
                1 if mesh is None else int(mesh.size))
            if layers:
                rec.counter("attn.flash_layers", float(flash))
                rec.counter("attn.layers", float(layers))
        if rec.enabled and "moe_pairs" in out[2]:
            rec.counter("moe.assignments", float(out[2]["moe_pairs"]))
            rec.counter("moe.max_load", float(out[2]["moe_max_load"]))
        return out


def make_serve_step(cfg):
    """Returns serve(params, caches, tokens, pos) ->
    (next_tokens, logits, new_caches) — one greedy decode step."""

    def serve(params, caches, tokens, pos):
        logits, new_caches = decode_step(cfg, params, caches, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, new_caches

    return serve
