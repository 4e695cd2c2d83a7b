"""The system under test: the program's compiled FPDT train step.

Built the way ``repro.launch.train.run`` builds it: the program's
``ModelConfig``, ``optim.adamw``, ``runtime.train_loop.make_train_step`` and,
on a mesh, ``launch.shardings`` with ``ParallelContext.for_mesh(...,
attn_impl="pallas")``.  Only the weights come from the benchmark
(``weights.py``), in the program's own parameter layout.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from perfbench import weights

# the configuration file's activation names -> the program's MLP kinds
MLP_KIND = {"gelu_new": "gelu", "silu": "swiglu"}


def model_config(model: dict, traffic: dict):
    """The program's ``ModelConfig`` for a configuration file and a traffic mix."""
    from repro.configs import ModelConfig

    u = traffic["chunks"]
    return ModelConfig(
        name=model["name"], family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        mlp_act=MLP_KIND[model["hidden_act"]], norm=model["norm"],
        rope_theta=model["rope_theta"], param_dtype=model["param_dtype"],
        opt_state_dtype=model["optimizer"]["state_dtype"],
        # the train CLI's rule: u attention chunks, 2u MLP chunks (paper 5.4)
        fpdt_chunks=u, mlp_chunks=2 * u, fpdt_offload=traffic["offload"],
        remat=traffic["remat"])


def mesh_shape(traffic: dict):
    """``None`` for one device, else (data, model) from ``"AxB"``."""
    spec = traffic.get("mesh")
    return None if spec is None else tuple(int(x) for x in spec.split("x"))


class Trainer:
    """One compiled step with its state, driven by the benchmark's loop."""

    def __init__(self, model: dict, traffic: dict, seed: int, devices):
        from repro.core.parallel import ParallelContext
        from repro.launch import shardings as SH
        from repro.launch.mesh import make_compat_mesh
        from repro.models import transformer as T
        from repro.optim import adamw
        from repro.runtime import train_loop
        from repro.runtime.placement import default_policy

        cfg = model_config(model, traffic)
        pol = default_policy()
        if traffic["offload"] and not pol.can_offload:
            raise RuntimeError(f"the traffic asks for offload and {pol} cannot offload")
        oc = adamw.OptConfig(**model["optimizer"])
        tc = train_loop.TrainConfig()
        expect = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
        made = jax.eval_shape(weights.make_fn(model), weights.seed_key(seed, 0))
        if jax.tree.structure(expect) != jax.tree.structure(made) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(expect), jax.tree.leaves(made))):
            raise RuntimeError("the benchmark's weight layout is not the program's")
        shape = mesh_shape(traffic)
        self.mesh, self.model = None, model
        if shape is None:
            par = psh = None
            self._init_opt = jax.jit(lambda p: adamw.init(oc, p))
            step = jax.jit(train_loop.make_train_step(cfg, par, oc, tc), donate_argnums=(0, 1))
            self.put = lambda b: {k: pol.put(jnp.asarray(v)) for k, v in b.items()}
        else:
            self.mesh = make_compat_mesh(shape, ("data", "model"), devices)
            par = ParallelContext.for_mesh(self.mesh, attn_impl="pallas", placement=pol)
            psh = SH.param_shardings(cfg, par, made)
            osh = SH.opt_shardings(cfg, par, jax.eval_shape(lambda p: adamw.init(oc, p), made), made)
            bsh = SH.batch_shardings(cfg, par, {
                k: jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]), jnp.int32)
                for k in ("tokens", "labels")})
            self._init_opt = jax.jit(lambda p: adamw.init(oc, p), out_shardings=osh)
            # no out_shardings: a program with host offload refuses them (train CLI)
            step = jax.jit(train_loop.make_train_step(cfg, par, oc, tc), donate_argnums=(0, 1),
                           in_shardings=(psh, osh, bsh))
            self.put = lambda b: {k: pol.put(jnp.asarray(v), bsh[k]) for k, v in b.items()}
        self._step, self._psh = step, psh
        self.compiled = None
        self.load(seed)

    def load(self, seed: int):
        """The seed's weights and fresh optimizer state (the compiled step stays)."""
        self.params = self.opt_state = None
        self.params = weights.make(self.model, seed, out_shardings=self._psh)
        self.opt_state = self._init_opt(self.params)

    def compile(self, batch: dict):
        with self._mesh_ctx():
            self.compiled = self._step.lower(self.params, self.opt_state, self.put(batch)).compile()
        return self.compiled

    def _mesh_ctx(self):
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def call(self, batch_on_device: dict):
        """Dispatch one step; the state moves on, the metrics are returned."""
        with self._mesh_ctx():
            self.params, self.opt_state, metrics = self.compiled(
                self.params, self.opt_state, batch_on_device)[:3]
        return metrics

    def free(self):
        self.params = self.opt_state = self.compiled = self._step = None
