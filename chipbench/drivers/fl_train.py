"""Driver of the FL train-step cells.

The timed path is the trainer's own step, ``dist.fl_step.ElasticFLStep``
on one pod: forward, loss, backward and the AdamW update, with params
and optimizer state donated.  Set-up makes the weights on the device in
one jitted call from the seed (the same function the reference uses),
a pool of token batches from the seed, builds the step, and drives it
through its first three steps on three different batches: those steps
compile the step and are the ones compared with the reference.  The
window then calls the same step object on the pool's next batches,
fetching each step's loss as the trainer does, until the time is up.

The check, after the window and with the program's state freed, runs
``references/qwen3.py`` over the same three batches in float32 at
highest precision and compares each step's loss, every leaf's norm of
the first gradient as AdamW took it (read back from the first moment
after step 1), and every leaf's norm of the weights' change after step
3, by the worst leaf.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from chipbench import flops
from chipbench.harness import span
from chipbench.references import qwen3 as ref


def arch_fields(c: dict) -> dict:
    """The program's ``ArchConfig`` fields for a Qwen3 dense config."""
    return dict(name=c["name"], family="dense",
                n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                n_heads=c["num_attention_heads"],
                n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                pattern=("global",), qk_norm=True, act=c["hidden_act"],
                tie_embeddings=c["tie_word_embeddings"],
                rope_theta=float(c["rope_theta"]),
                norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"])


def to_program_layout(w: dict, dtype) -> dict:
    """Reference-layout weights as the program's params tree: layers
    stacked on a leading axis under ``cycles/slot0``."""
    import jax.numpy as jnp
    return {"embed": w["embed"].astype(dtype),
            "final_norm": w["final_norm"].astype(dtype),
            "cycles": {"slot0": {k: jnp.stack([lay[k] for lay in w["layers"]]
                                              ).astype(dtype)
                                 for k in ref.LAYER_KEYS}},
            "tail": []}


def program_leaf_norms(tree: dict, n_layers: int):
    """Norms of a program-layout tree's leaves, in the reference's
    :func:`leaf_names` order (stacked leaves split per layer)."""
    import jax.numpy as jnp

    def nrm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    vals = [nrm(tree["embed"]), nrm(tree["final_norm"])]
    st = tree["cycles"]["slot0"]
    for i in range(n_layers):
        vals += [nrm(st[k][i]) for k in ref.LAYER_KEYS]
    return jnp.stack(vals)


def _host_delta_norms(master: dict, w0: dict, n_layers: int) -> np.ndarray:
    """Norms of ``master - w0`` per leaf, on the host, in the
    reference's :func:`leaf_names` order."""
    def nrm(a, b):
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        return float(np.sqrt(np.sum(np.square(d, dtype=np.float64))))

    out = [nrm(master["embed"], w0["embed"]),
           nrm(master["final_norm"], w0["final_norm"])]
    ms, ws = master["cycles"]["slot0"], w0["cycles"]["slot0"]
    for i in range(n_layers):
        out += [nrm(ms[k][i], ws[k][i]) for k in ref.LAYER_KEYS]
    return np.asarray(out)


def leaf_gap(prog, refv, keep=None) -> float:
    """Widest gap between the program's and the reference's leaf norms,
    each over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    prog = np.asarray(prog, np.float64)
    refv = np.asarray(refv, np.float64)
    if keep is None:
        keep = np.ones(refv.size, bool)
    den = np.maximum(refv, np.median(refv[keep]))
    return float(np.max(np.abs(prog - refv)[keep] / den[keep]))


def moved_leaves(grad_raw) -> np.ndarray:
    """Leaves whose first reference gradient is above a thousandth of
    the median leaf's: the others move under Adam by round-off."""
    g = np.asarray(grad_raw, np.float64)
    return g >= 1e-3 * np.median(g)


class Driver:
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
        self.opt_hp = dict(tr["optimizer"])
        self.limits = self.c["limits"]
        self.L = int(self.c["num_hidden_layers"])
        self.arch = ArchConfig(**arch_fields(self.c))
        self.step_flops = flops.train_step_flops(self.arch, self.batch,
                                                 self.seq)
        self.window_losses: list[float] = []
        self._gen = None

    # ------------------------------------------------------------------
    def _pool(self):
        """``pool`` token batches from the seed: inputs and next-token
        labels of uniformly drawn sequences, one jitted call."""
        import jax
        import jax.numpy as jnp
        V, B, T = self.c["vocab_size"], self.batch, self.seq

        @jax.jit
        def make(key):
            toks = jax.random.randint(key, (self.pool_n, B, T + 1), 0, V,
                                      dtype=jnp.int32)
            return toks[..., :-1], toks[..., 1:]

        key = jax.random.key(ref.seed32(self.seed) ^ 0x7A11)
        return make(key)

    def _weights(self):
        """The seed's weights in the program's layout and dtype: one
        jitted call, the same program for every seed."""
        import jax
        import jax.numpy as jnp
        if self._gen is None:
            dt = jnp.dtype(self.arch.dtype)
            self._gen = jax.jit(lambda key: to_program_layout(
                ref.init_weights(self.c, self.L, key), dt))
        return self._gen(ref.weight_key(self.seed))

    def _batch(self, i: int) -> dict:
        k = i % self.pool_n
        return {"inputs": self.inputs[k][None], "labels": self.labels[k][None]}

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.dist.fl_step import ElasticFLStep
        from repro.launch.mesh import make_host_mesh
        from repro.models import init_params
        from repro.optim import adamw_init
        from repro.optim.schedules import constant_lr

        from chipbench.harness import BenchError

        self.inputs, self.labels = self._pool()
        params = self._weights()
        want = jax.tree_util.tree_structure(jax.eval_shape(
            functools.partial(init_params, self.arch), jax.random.key(0)))
        if jax.tree_util.tree_structure(params) != want:
            raise BenchError("the program's params tree is not the layout "
                             "this driver builds")
        # The initial weights wait on the host for the change after step
        # 3: a second device copy would raise the chip's memory peak.
        w0 = jax.device_get(params)
        opt = adamw_init(params)
        hp = self.opt_hp
        self.step = ElasticFLStep(
            self.arch, lr_schedule=constant_lr(hp["lr"]),
            mesh_factory=lambda p: make_host_mesh((1, 1), ("data", "model")))
        self.ones = jnp.ones((1,))
        norms = jax.jit(functools.partial(program_leaf_norms,
                                          n_layers=self.L))
        losses = []
        for t in range(self.N_COMPARED):
            params, opt, m = self.step(params, opt, self._batch(t),
                                       self.ones, self.ones)
            losses.append(float(m["loss"]))
            if t == 0:
                # The first moment after one step is (1 - b1) times the
                # gradient as AdamW took it (clipped).
                self.grad_p = np.asarray(norms(opt.m), np.float64) / (
                    1.0 - hp["b1"])
        self.delta_p = _host_delta_norms(jax.device_get(opt.master), w0,
                                         self.L)
        del w0
        self.loss_p = np.asarray(losses)
        self.params, self.opt = params, opt
        self.next_batch = self.N_COMPARED

    def window(self, seconds: float) -> dict:
        params, opt = self.params, self.opt
        self.params = self.opt = None
        i = self.next_batch
        losses = []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            with span("bench.step", self.trace):
                params, opt, m = self.step(params, opt, self._batch(i),
                                           self.ones, self.ones)
                losses.append(float(m["loss"]))
            i += 1
        t1 = time.perf_counter()
        self.params, self.opt = params, opt
        self.window_losses = losses
        steps = len(losses)
        return {"elapsed_s": t1 - t0, "steps": steps,
                "tokens": steps * self.batch * self.seq,
                "model_flops": steps * self.step_flops}

    @staticmethod
    def end_to_end(stats: dict) -> dict:
        return {"tokens_per_s": stats["tokens"] / stats["elapsed_s"]}

    # ------------------------------------------------------------------
    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.params = self.opt = None
        self.step = None
        gc.collect()

    def reference(self, dtype=None, batches=None) -> dict:
        if batches is None:
            batches = [(self.inputs[t], self.labels[t])
                       for t in range(self.N_COMPARED)]
        return ref.run_steps(self.c, self.seed, self.L, batches,
                             self.opt_hp, dtype=dtype,
                             steps=self.N_COMPARED)

    @staticmethod
    def gaps(loss, grad, delta, r: dict) -> dict:
        keep = moved_leaves(r["grad_raw"])
        return {"loss_gap": float(np.max(np.abs(np.asarray(loss) - r["losses"])
                                         / np.abs(r["losses"]))),
                "grad_gap": leaf_gap(grad, r["grad"]),
                "update_gap": leaf_gap(delta, r["delta"], keep)}

    def check(self) -> tuple[dict, int, int]:
        self.release()
        r = self.reference()
        g = self.gaps(self.loss_p, self.grad_p, self.delta_p, r)
        checks = {k: {"value": v, "limit": self.limits[k]}
                  for k, v in g.items() if k in self.limits}
        bad_window = int(sum(not np.isfinite(x) for x in self.window_losses))
        over = any(c["value"] > c["limit"] for c in checks.values())
        attempted = self.N_COMPARED + len(self.window_losses)
        failed = bad_window + (self.N_COMPARED if over else 0)
        return checks, attempted, failed

    def calibrate(self, control: bool) -> dict:
        """Readings that set the limits: the program's, and with
        ``control`` the control's (the reference in float8 in the
        program's place) and the faults' (the reference with half the
        batch left out, and with one input token altered)."""
        import jax.numpy as jnp
        self.release()
        r = self.reference()
        out = {"program": self.gaps(self.loss_p, self.grad_p, self.delta_p,
                                    r)}
        if control:
            def versus(x):
                return self.gaps(x["losses"], x["grad"], x["delta"], r)
            out["control"] = versus(self.reference(dtype=jnp.float8_e4m3fn))
            half = [(self.inputs[t][: self.batch // 2],
                     self.labels[t][: self.batch // 2])
                    for t in range(self.N_COMPARED)]
            out["fault_half_batch"] = versus(self.reference(batches=half))
            inp0 = self.inputs[0].at[0, self.seq // 2].set(
                (self.inputs[0][0, self.seq // 2] + 1) % self.c["vocab_size"])
            tok = [(inp0, self.labels[0])] + [
                (self.inputs[t], self.labels[t])
                for t in range(1, self.N_COMPARED)]
            out["fault_token"] = versus(self.reference(batches=tok))
        return out
