"""The system under test: the program's compiled FPDT train step.

Built the way ``repro.launch.train.run`` builds it: the program's
``ModelConfig``, ``optim.adamw``, ``runtime.train_loop.make_train_step`` and,
on a mesh, ``launch.shardings`` with ``ParallelContext.for_mesh(...,
attn_impl="pallas")``.  Only the weights come from the benchmark
(``weights.py``), in the program's own parameter layout.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from perfbench import weights

# the configuration file's activation names -> the program's MLP kinds
MLP_KIND = {"gelu_new": "gelu", "silu": "swiglu"}
NORMS = ("layernorm", "rmsnorm")
# keys the step is built from (``optimizer`` also makes the ``OptConfig``)
CARRIED = {"name", "num_hidden_layers", "hidden_size", "num_attention_heads",
           "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size", "hidden_act",
           "norm", "norm_eps", "position_embedding", "rope_theta", "tie_word_embeddings",
           "param_dtype", "optimizer"}
# keys that describe the configuration and set nothing the step computes
DESCRIPTIVE = {"source", "deployment", "published", "reduced", "assumed"}
# the program's eps, read back, may differ from the file's by rounding only
EPS_RTOL = 1e-2


class Refused(ValueError):
    """A configuration the benchmark cannot run through the program as stated."""

    def __init__(self, key: str, why: str):
        super().__init__(f"configuration key {key!r}: {why}")
        self.key = key


def norm_eps_of(cfg, near: float) -> float:
    """The eps the program's ``apply_norm`` adds under ``cfg``, read back from it.

    On a row of +-c (mean 0, mean square c^2) with unit weights and no bias
    both LayerNorm and RMSNorm give y = c / sqrt(c^2 + eps); with c^2 = ``near``
    an eps near it is read to float32's precision."""
    from repro.models import layers

    c = near ** 0.5
    x = c * jnp.where(jnp.arange(cfg.d_model) % 2 == 0, 1.0, -1.0)[None]
    p = {"w": jnp.ones((cfg.d_model,), jnp.float32), "b": jnp.zeros((cfg.d_model,), jnp.float32)}
    y = float(jax.jit(lambda p, x: layers.apply_norm(cfg, p, x))(p, x)[0, 0])
    return c * c * (1.0 / (y * y) - 1.0)


def model_config(model: dict, traffic: dict):
    """The program's ``ModelConfig`` for a configuration file and a traffic mix.

    Raises ``Refused``, naming the key, for a key it neither carries nor
    knows as descriptive, and for a value the program or this benchmark's
    weights and reference cannot run."""
    from repro.configs import ModelConfig

    unknown = sorted(set(model) - CARRIED - DESCRIPTIVE)
    if unknown:
        raise Refused(unknown[0], "the benchmark does not carry it into the program "
                      f"(all such keys: {unknown})")
    if model["hidden_act"] not in MLP_KIND:
        raise Refused("hidden_act", f"{model['hidden_act']!r} is none of {sorted(MLP_KIND)}")
    if model["norm"] not in NORMS:
        raise Refused("norm", f"{model['norm']!r} is none of {list(NORMS)}")
    if model["position_embedding"] != "rope":
        raise Refused("position_embedding", f"{model['position_embedding']!r}: the program's "
                      "dense model has rotary positions only")
    tied = model.get("tie_word_embeddings", False)
    if tied:
        raise Refused("tie_word_embeddings", "the benchmark's weights and reference have an "
                      "untied head")
    u = traffic["chunks"]
    kw = dict(
        name=model["name"], family="dense",
        num_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        mlp_act=MLP_KIND[model["hidden_act"]], norm=model["norm"],
        rope_theta=model["rope_theta"], tie_embeddings=tied, param_dtype=model["param_dtype"],
        opt_state_dtype=model["optimizer"]["state_dtype"],
        # the train CLI's rule: u attention chunks, 2u MLP chunks (paper 5.4)
        fpdt_chunks=u, mlp_chunks=2 * u, fpdt_offload=traffic["offload"],
        remat=traffic["remat"])
    # ModelConfig has no eps yet and the program's norms fix their own
    # (PERF.md, Open questions); once it has the field, it gets the file's
    if "norm_eps" in {f.name for f in dataclasses.fields(ModelConfig)}:
        kw["norm_eps"] = model["norm_eps"]
    cfg = ModelConfig(**kw)
    got = norm_eps_of(cfg, model["norm_eps"])
    if abs(got / model["norm_eps"] - 1.0) > EPS_RTOL:
        raise Refused("norm_eps", f"{model['norm_eps']!r}: the program's {cfg.norm} adds "
                      f"eps {got:.4g}")
    return cfg


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
