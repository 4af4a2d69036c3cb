"""Driver of the FL train cells of a DeepSeek-V3 MoE stage (Moonlight).

The timed path is the trainer's own step, as in ``fl_train.py``:
``dist.fl_step.ElasticFLStep`` on one pod, through ``models.train_loss``
with latent attention, the leading dense layer, and MoE layers that
route over all the router's outputs and compute the held experts' part
without dropping a pair.  Set-up makes the weights on the device from
the seed (the reference's generator), a pool of token batches whose ids
follow a Zipf law over the vocabulary slice (ranks permuted by a fixed
seed, so the same ids are frequent in every run), builds the step and
drives it through its first three steps: those compile it and are the
ones compared with the reference.  The window then calls the same step
object on the pool's next batches, fetching each step's loss, until the
time is up; a traced window also records the step's routing counters
(``moe.assignments``, ``moe.max_load``).

The check runs ``references/moonlight.py`` over the same three batches
in float32 at highest precision, and compares as ``fl_train.py`` does:
each step's loss, every trained leaf's norm of the first gradient as
AdamW took it, and of the weights' change after step 3.  The routers'
correction biases are buffers, in neither comparison.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

from chipbench import flops_moe
from chipbench.drivers import fl_train
from chipbench.harness import BenchError
from chipbench.references import moonlight as ref


def arch_fields(c: dict) -> dict:
    """The program's ``ArchConfig`` fields for a DeepSeek-V3 config
    that holds experts ``experts_first`` on of ``router_outputs``."""
    if (c["topk_method"], c["n_group"], c["topk_group"], c["q_lora_rank"],
            c["moe_layer_freq"]) != ("noaux_tc", 1, 1, None, 1):
        raise BenchError("the program routes noaux_tc with one group, "
                         "no query latent, every layer after the dense "
                         "ones an MoE layer")
    return dict(name=c["name"], family="moe",
                n_layers=c["num_hidden_layers"],
                first_dense=c["first_k_dense_replace"],
                d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
                n_kv=c["num_attention_heads"],
                head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                kv_lora_rank=c["kv_lora_rank"],
                qk_nope_dim=c["qk_nope_head_dim"],
                qk_rope_dim=c["qk_rope_head_dim"],
                v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
                vocab=c["vocab_size"], pattern=("moe",),
                n_experts=c["router_outputs"],
                top_k=c["num_experts_per_tok"],
                d_expert=c["moe_intermediate_size"],
                d_shared=c["n_shared_experts"] * c["moe_intermediate_size"],
                router=c["scoring_func"],
                routed_scale=c["routed_scaling_factor"],
                expert_first=c["experts_first"],
                experts_held=c["n_routed_experts"], act=c["hidden_act"],
                tie_embeddings=c["tie_word_embeddings"], embed_scale=False,
                rope_theta=float(c["rope_theta"]),
                norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"])


def to_program_layout(c: dict, w: dict, dtype) -> dict:
    """Reference-layout weights as the program's params tree: the dense
    layers under ``lead``, the MoE layers stacked under ``cycles/slot0``
    with their correction biases under ``buffers``."""
    import jax.numpy as jnp
    nd = c["first_k_dense_replace"]
    moe = w["layers"][nd:]
    slot = {k: jnp.stack([lay[k] for lay in moe]).astype(
        jnp.float32 if k == "router" else dtype) for k in ref.MOE_KEYS}
    slot["buffers"] = {"router_bias": jnp.stack(w["router_bias"])}
    return {"embed": w["embed"].astype(dtype), "head": w["head"].astype(dtype),
            "final_norm": w["final_norm"].astype(dtype),
            "lead": [{k: w["layers"][i][k].astype(dtype)
                      for k in ref.DENSE_KEYS} for i in range(nd)],
            "cycles": {"slot0": slot}, "tail": []}


def _program_leaves(c: dict, tree: dict, n_layers: int) -> list:
    """A program-layout tree's trained leaves, in the reference's
    :func:`leaf_names` order (stacked leaves split per layer)."""
    nd = c["first_k_dense_replace"]
    out = [tree["embed"], tree["head"], tree["final_norm"]]
    for i in range(nd):
        out += [tree["lead"][i][k] for k in ref.DENSE_KEYS]
    st = tree["cycles"]["slot0"]
    for i in range(n_layers - nd):
        out += [st[k][i] for k in ref.MOE_KEYS]
    return out


def program_leaf_norms(c: dict, tree: dict, n_layers: int):
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in _program_leaves(c, tree, n_layers)])


def _host_delta_norms(c, master, w0, n_layers) -> np.ndarray:
    def nrm(a, b):
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        return float(np.sqrt(np.sum(np.square(d, dtype=np.float64))))

    return np.asarray([nrm(a, b) for a, b in zip(
        _program_leaves(c, master, n_layers),
        _program_leaves(c, w0, n_layers))])


class Driver(fl_train.Driver):
    N_COMPARED = 3

    def __init__(self, cell, seed: int, meter, trace: bool):
        from repro.models import ArchConfig
        self.cell = cell
        self.seed = int(seed)
        self.meter = meter
        self.trace = trace
        self.c = dict(cell.config)
        tr = cell.traffic
        self.batch, self.seq = int(tr["batch"]), int(tr["seq"])
        self.pool_n = int(tr["pool"])
        self.zipf, self.rank_seed = float(tr["zipf"]), int(tr["rank_seed"])
        self.opt_hp = dict(tr["optimizer"])
        self.limits = self.c["limits"]
        self.L = int(self.c["num_hidden_layers"])
        try:
            self.arch = ArchConfig(**arch_fields(self.c))
        except TypeError as e:
            raise BenchError("the program's ArchConfig cannot describe "
                             f"this model: {e}") from e
        self.step_flops = flops_moe.train_step_flops(self.c, self.batch,
                                                     self.seq)
        self.window_losses: list[float] = []
        self._gen = None

    # ------------------------------------------------------------------
    def _pool(self):
        """``pool`` token batches from the seed, ids Zipf-distributed
        over the vocabulary: rank r drawn with weight r^-zipf by the
        inverse of its distribution, mapped to an id by a permutation
        fixed by ``rank_seed``.  One jitted call."""
        import jax
        import jax.numpy as jnp
        V, B, T = self.c["vocab_size"], self.batch, self.seq

        @jax.jit
        def make(key):
            cdf = jnp.cumsum(jnp.arange(1, V + 1, dtype=jnp.float32)
                             ** -self.zipf)
            u = jax.random.uniform(key, (self.pool_n, B, T + 1)) * cdf[-1]
            rank = jnp.minimum(jnp.searchsorted(cdf, u), V - 1)
            ids = jax.random.permutation(jax.random.key(self.rank_seed), V)
            toks = ids[rank].astype(jnp.int32)
            return toks[..., :-1], toks[..., 1:]

        key = jax.random.key(ref.seed32(self.seed) ^ 0x7A11)
        return make(key)

    def _weights(self):
        import jax
        import jax.numpy as jnp
        if self._gen is None:
            dt = jnp.dtype(self.arch.dtype)
            self._gen = jax.jit(lambda key: to_program_layout(
                self.c, ref.init_weights(self.c, self.L, key), dt))
        return self._gen(ref.weight_key(self.seed))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.dist.fl_step import ElasticFLStep
        from repro.launch.mesh import make_host_mesh
        from repro.models import init_params
        from repro.optim import adamw_init
        from repro.optim.schedules import constant_lr

        self.inputs, self.labels = self._pool()
        params = self._weights()
        want = jax.tree_util.tree_structure(jax.eval_shape(
            functools.partial(init_params, self.arch), jax.random.key(0)))
        if jax.tree_util.tree_structure(params) != want:
            raise BenchError("the program's params tree is not the layout "
                             "this driver builds")
        w0 = jax.device_get(params)
        opt = adamw_init(params)
        hp = self.opt_hp
        self.step = ElasticFLStep(
            self.arch, lr_schedule=constant_lr(hp["lr"]),
            mesh_factory=lambda p: make_host_mesh((1, 1), ("data", "model")))
        self.ones = jnp.ones((1,))
        norms = jax.jit(functools.partial(program_leaf_norms, self.c,
                                          n_layers=self.L))
        losses = []
        for t in range(self.N_COMPARED):
            params, opt, m = self.step(params, opt, self._batch(t),
                                       self.ones, self.ones)
            losses.append(float(m["loss"]))
            if t == 0:
                self.grad_p = np.asarray(norms(opt.m), np.float64) / (
                    1.0 - hp["b1"])
        self.delta_p = _host_delta_norms(self.c, jax.device_get(opt.master),
                                         w0, self.L)
        del w0
        self.loss_p = np.asarray(losses)
        self.params, self.opt = params, opt
        self.next_batch = self.N_COMPARED

    def window(self, seconds: float) -> dict:
        from repro import obs
        rec = obs.Recorder() if self.trace else None
        with (obs.recording(rec) if rec is not None
              else contextlib.nullcontext()):
            stats = super().window(seconds)
        stats["counters"] = ({k: m["value"] for k, m in rec.metrics.items()
                              if m["metric"] == "counter"}
                             if rec is not None else {})
        stats["moe_layers"] = self.arch.layer_kinds.count("moe")
        return stats

    # ------------------------------------------------------------------
    def reference(self, dtype=None, batches=None, c=None) -> dict:
        if batches is None:
            batches = [(self.inputs[t], self.labels[t])
                       for t in range(self.N_COMPARED)]
        return ref.run_steps(self.c if c is None else c, self.seed, self.L,
                             batches, self.opt_hp, dtype=dtype,
                             steps=self.N_COMPARED)

    def calibrate(self, control: bool) -> dict:
        """``fl_train``'s readings, and with ``control`` also those of a
        reference whose routers ignore the correction bias."""
        out = super().calibrate(control)
        if control:
            r = self.reference()
            x = self.reference(c=dict(self.c, router_bias_std=0.0))
            out["fault_bias_ignored"] = self.gaps(x["losses"], x["grad"],
                                                  x["delta"], r)
        return out
