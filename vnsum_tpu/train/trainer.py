"""Sharded training step (fine-tuning / continued pretraining of the
summarization model).

The reference is inference-only — it has no optimizer, no checkpoints, no
training loop at all (SURVEY.md §5 "no state-dict/optimizer checkpoints").
This module makes training a first-class capability the TPU-native way: one
jit-compiled step over a (data, model, seq) mesh — DP via batch sharding, TP
via the megatron param specs, SP via ring attention — with optax AdamW,
gradient clipping, remat inside the layer scan, and donated buffers.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.logging import get_logger
from ..models.llama import LlamaConfig, forward_train, init_params
from ..parallel.mesh import AXES
from ..parallel.ring import ring_attention
from ..parallel.sharding import param_shardings, param_specs

logger = get_logger("vnsum.train")


def lm_loss(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,      # [B, S]
    loss_mask: jax.Array,   # [B, S] bool — positions whose NEXT token counts
    *,
    attention_fn=None,
    remat: bool = True,
) -> jax.Array:
    """Next-token cross-entropy, mean over unmasked positions."""
    logits = forward_train(
        params, cfg, tokens, attention_fn=attention_fn, remat=remat
    )
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    mask = loss_mask[:, :-1].astype(jnp.float32)
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 1e-5
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    remat: bool = True
    context_parallel: bool = False  # ring attention over the seq axis
    fsdp: bool = False  # shard stacked layers (+ their optimizer state)
    #                     over the mesh `fsdp` axis, ZeRO-3 style


class Trainer:
    def __init__(
        self,
        model_config: LlamaConfig,
        mesh: Mesh,
        train_config: TrainConfig | None = None,
        params: dict | None = None,
        seed: int = 0,
    ) -> None:
        from ..core.jax_cache import enable_compilation_cache

        enable_compilation_cache()
        self.cfg = model_config
        self.mesh = mesh
        self.tc = train_config or TrainConfig()
        self.step_count = 0

        self.optimizer = optax.chain(
            optax.clip_by_global_norm(self.tc.grad_clip),
            optax.adamw(
                self.tc.learning_rate,
                b1=self.tc.b1,
                b2=self.tc.b2,
                weight_decay=self.tc.weight_decay,
            ),
        )

        if self.tc.fsdp:
            if AXES.fsdp not in mesh.shape:
                raise ValueError(
                    "TrainConfig.fsdp=True needs a mesh with an 'fsdp' axis "
                    "(make_mesh({'fsdp': N, ...}))"
                )
            if self.cfg.n_layers % mesh.shape[AXES.fsdp]:
                raise ValueError(
                    f"n_layers={self.cfg.n_layers} not divisible by the "
                    f"fsdp axis ({mesh.shape[AXES.fsdp]})"
                )
        p_shardings = param_shardings(
            mesh, self.cfg.tie_embeddings, fsdp=self.tc.fsdp,
            qk_norm=self.cfg.qk_norm,
            sandwich_norms=self.cfg.sandwich_norms,
            looped=self.cfg.loop_passes > 1,
        )
        if params is None:
            # init directly into the sharded layout: each leaf is produced
            # under jit with its target sharding, so a 2-chip mesh never
            # materializes the full replicated model on one device
            init_fn = jax.jit(
                partial(init_params, cfg=self.cfg), out_shardings=p_shardings
            )
            params = init_fn(jax.random.key(seed))
        else:
            params = jax.tree.map(jax.device_put, params, p_shardings)
        self.params = params

        opt_specs = self._opt_state_specs()
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.opt_state = jax.jit(
            self.optimizer.init, out_shardings=opt_shardings
        )(self.params)

        attention_fn = None
        if self.tc.context_parallel:
            attention_fn = partial(ring_attention, mesh=mesh)

        # with fsdp the batch shards over BOTH axes, so the fsdp axis also
        # acts as data parallelism (true ZeRO-3: partitioned compute plus
        # sharded params/optimizer) instead of replicating the forward and
        # doing fsdp-fold redundant FLOPs for a memory-only win
        batch_axes = (AXES.data, AXES.fsdp) if self.tc.fsdp else AXES.data
        data_spec = NamedSharding(mesh, P(batch_axes, None))

        def step(params, opt_state, tokens, loss_mask):
            loss, grads = jax.value_and_grad(lm_loss)(
                params, self.cfg, tokens, loss_mask,
                attention_fn=attention_fn, remat=self.tc.remat,
            )
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        self._step = jax.jit(
            step,
            in_shardings=(p_shardings, opt_shardings, data_spec, data_spec),
            out_shardings=(p_shardings, opt_shardings, None),
            donate_argnums=(0, 1),
        )

    def _opt_state_specs(self):
        """PartitionSpecs for the optax state: any state subtree that has the
        params' exact tree structure (AdamW mu/nu) inherits the param specs;
        every other leaf (counters, empty states) replicates."""
        specs = param_specs(
            self.cfg.tie_embeddings, fsdp=self.tc.fsdp,
            qk_norm=self.cfg.qk_norm,
            sandwich_norms=self.cfg.sandwich_norms,
            looped=self.cfg.loop_passes > 1,
        )
        abstract = jax.eval_shape(
            lambda: init_params(jax.random.key(0), self.cfg)
        )
        params_def = jax.tree.structure(abstract)
        state_shape = jax.eval_shape(self.optimizer.init, abstract)

        def is_param_subtree(x):
            if isinstance(x, jax.ShapeDtypeStruct):
                return False
            try:
                return jax.tree.structure(x) == params_def
            except Exception:
                return False

        return jax.tree.map(
            lambda x: specs if is_param_subtree(x) else P(),
            state_shape,
            is_leaf=is_param_subtree,
        )

    def step(self, tokens, loss_mask=None):
        """One optimizer step; tokens [B, S] int32. Returns float loss."""
        tokens = jnp.asarray(tokens, jnp.int32)
        batch_div = self.mesh.shape.get(AXES.data, 1)
        if self.tc.fsdp:
            batch_div *= self.mesh.shape.get(AXES.fsdp, 1)
        if tokens.shape[0] % batch_div:
            raise ValueError(
                f"batch size {tokens.shape[0]} must be divisible by "
                f"data{'×fsdp' if self.tc.fsdp else ''} mesh axes ({batch_div}); "
                "with fsdp=True the batch shards over both axes"
            )
        if loss_mask is None:
            loss_mask = jnp.ones_like(tokens, dtype=bool)
        t0 = time.time()
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, tokens, loss_mask
        )
        loss = float(loss)
        self.step_count += 1
        logger.info(
            "step %d: loss=%.4f (%.2fs)", self.step_count, loss, time.time() - t0
        )
        return loss
