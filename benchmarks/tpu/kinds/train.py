"""Training job: the program's compiled train step in a closed loop.

The window drives ``repro.launch.steps.make_train_step`` jitted as
``repro.launch.train.main`` jits it: the state in its shardings on a host
mesh, params and optimizer state donated, the divergence guard's
``max_loss`` passed each step, and the next step issued once the host has
read the loss and the ``applied`` verdict.  ``train.main``'s own loop has
no entry for one step, so it is not on the measured path.

Set-up builds one state from ``--seed``, compiles the step once (ahead of
time, which gives its ``memory_analysis()``), and drives that state through
the first ``ref_steps`` steps with the window's own call and feed; the
window then continues the same state.  Those first steps are what the
float32 reference follows after the window.
"""
from __future__ import annotations

import math
import statistics
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops, reference, weights
from ..feed import LMFeed

# Keys of a configuration file and the program's ModelConfig fields they
# set.  ``head_dim`` defaults to hidden_size / num_attention_heads.
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "head_dim": "head_dim", "rms_norm_eps": "norm_eps",
           "rope_theta": "rope_theta", "qkv_bias": "qkv_bias",
           "tie_word_embeddings": "tie_embeddings"}
# Leaves whose reference gradient is below this share of the median
# leaf's move by round-off alone under Adam; they are not compared.
NEGLIGIBLE_GRAD = 1e-3


def program_config(conf: dict, job: dict):
    """The program's ModelConfig, set from the configuration file."""
    from repro import configs
    if not conf["tie_word_embeddings"]:
        raise ValueError("the benchmark's weights assume a tied embedding")
    conf = {**conf, "head_dim": conf.get(
        "head_dim", conf["hidden_size"] // conf["num_attention_heads"])}
    return configs.get(conf["program_arch"]).replace(
        **{f: conf[k] for k, f in _FIELDS.items()},
        remat=job["remat"], dtype=job["dtype"],
        param_dtype=job["param_dtype"])


def relative_gaps(prog: dict, ref: dict, keep) -> dict:
    """``|prog - ref|`` per leaf, over the larger of the reference's norm of
    that leaf and of the median leaf."""
    med = statistics.median(ref[k] for k in keep)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def kept_leaves(ref_grad_norms: dict) -> list:
    med = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items()
            if v >= NEGLIGIBLE_GRAD * med]


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers of a train cell, from program and reference
    readings (``losses``, ``grad_norms``, ``change_norms``)."""
    keep = kept_leaves(ref["grad_norms"])
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    grad = relative_gaps(prog["grad_norms"], ref["grad_norms"], keep)
    upd = relative_gaps(prog["change_norms"], ref["change_norms"], keep)
    return {"loss_gap": loss, "grad_gap": max(grad.values()),
            "update_gap": max(upd.values())}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda t: jnp.linalg.norm(
        t.astype(jnp.float32).reshape(-1)), tree)


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.linalg.norm(
        (a.astype(jnp.float32) - b.astype(jnp.float32)).reshape(-1)),
        new, old)


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): float(v) for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


class Job:
    def __init__(self, cell):
        self.cell, self.conf, self.job = cell, cell.conf, cell.job
        self.seq, self.batch = self.job["seq"], self.job["batch"]
        self.flops_per_step = (flops.lm_train_flops_per_token(
            self.conf, self.seq) * self.batch * self.seq)
        self.feed = LMFeed(vocab=self.conf["vocab_size"], batch=self.batch,
                           seq=self.seq, seed=cell.seed,
                           zipf_a=self.job["zipf_a"])
        self.counters = {}
        self.attempted = self.failed = 0

    # -- set-up ---------------------------------------------------------
    def setup(self):
        from repro.distributed.monitor import DivergenceGuard
        from repro.distributed.sharding import mesh_context
        from repro.launch import steps
        from repro.launch.mesh import make_host_mesh
        from repro.models import model as M
        from repro.optim import adamw, cosine_schedule

        cell, conf, job = self.cell, self.conf, self.job
        o = job["optimizer"]
        self.cfg = cfg = program_config(conf, job)
        self.opt = adamw(lr=cosine_schedule(o["lr"], warmup=o["warmup"],
                                            total=o["total"],
                                            floor=o["floor"]),
                         b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"])
        self.mesh = make_host_mesh(n_devices=cell.chips)
        self.device_count = self.mesh.size
        self.guard = DivergenceGuard()
        self._ctx = mesh_context(self.mesh)
        self._ctx.__enter__()
        p_sh, o_sh = steps.state_shardings(cfg, self.mesh, self.opt.name)
        self.init = weights.lm_init(conf, p_sh)
        key = weights.seed_key(cell.seed)
        params = self.init(key)
        want = jax.tree.map(lambda s: (s.shape, s.dtype),
                            jax.eval_shape(partial(M.init_params, cfg), key))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != got:
            raise ValueError(f"benchmark weights do not match the "
                             f"program's parameters: {got} vs {want}")
        opt_state = jax.jit(self.opt.init, out_shardings=o_sh)(params)
        first = self.feed.batch_at(0)
        self.b_sh = steps.batch_shardings(cfg, self.mesh,
                                          {"tokens": first})
        step = jax.jit(steps.make_train_step(cfg, self.opt,
                                             max_grad_norm=o["clip"]),
                       out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1))
        self.step_fn = step.lower(
            params, opt_state, self._put(first),
            np.float32(math.inf)).compile()
        ma = self.step_fn.memory_analysis()
        self.hbm_bytes = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                          + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        self.params, self.opt_state = params, opt_state

        # The first steps: the window's own call and feed.
        self.prog = {"losses": []}
        b1 = o["b1"]
        for i in range(job["ref_steps"]):
            self._train(i)
            if i == 0:
                m = _by_path(_norms(self.opt_state.inner["m"]))
                self.prog["grad_norms"] = {k: v / (1 - b1)
                                           for k, v in m.items()}
        p0 = self.init(key)
        self.prog["change_norms"] = _by_path(_change_norms(self.params, p0))
        del p0
        self.attempted = self.failed = 0

    def _put(self, tokens):
        return jax.device_put({"tokens": tokens}, self.b_sh)

    def _train(self, i: int) -> int:
        span = self.span
        with span("bench.feed"):
            batch = self._put(self.feed.batch_at(i))
        with span("bench.train_step"):
            self.params, self.opt_state, m = self.step_fn(
                self.params, self.opt_state, batch,
                np.float32(self.guard.max_loss()))
            loss, applied = float(m["loss"]), bool(m["applied"])
        self.guard.record(applied, loss)
        self.attempted += 1
        if not (applied and math.isfinite(loss)):
            self.failed += 1
        if i < self.job["ref_steps"]:
            self.prog["losses"].append(loss)
        return self.batch * self.seq

    # -- the window -----------------------------------------------------
    def step(self, i: int) -> int:
        return self._train(self.job["ref_steps"] + i)

    def memory_peak_bytes(self) -> int:
        stats = jax.devices()[0].memory_stats() or {}
        return int(max(stats.get("peak_bytes_in_use", 0), self.hbm_bytes))

    # -- correctness ----------------------------------------------------
    def free_program(self):
        for a in jax.tree.leaves((self.params, self.opt_state)):
            a.delete()
        del self.params, self.opt_state, self.step_fn
        self._ctx.__exit__(None, None, None)

    def reference_readings(self, precision: str = "float32",
                           fault: str | None = None) -> dict:
        """The reference over the first steps, on weights it makes from the
        seed itself."""
        conf, job = self.conf, self.job
        init = weights.lm_init(conf)
        key = weights.seed_key(self.cell.seed)
        ref = reference.LMReference(conf, precision, rows=job["ref_rows"],
                                    head_tokens=job["ref_head_tokens"])
        return reference.lm_train_readings(
            ref, job["optimizer"], lambda: init(key),
            [self.feed.batch_at(i) for i in range(job["ref_steps"])],
            fault=fault)

    def check(self) -> dict:
        self.free_program()
        gaps = compare(self.prog, self.reference_readings())
        lim = self.cell.limits
        out = {k: (v, lim.get(k)) for k, v in gaps.items()
               if not (k in lim and lim[k] is None)}
        out["hbm_gib"] = (self.hbm_bytes / 2**30,
                          self.job["hbm_budget_gib"])
        return out
