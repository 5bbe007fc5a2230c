"""Model facade: ``build_model(cfg)`` -> init / loss / prefill / decode.

This is the single entry point the launcher, dry-run, tests and examples
use; arch-specific wiring lives in transformer.py / mamba.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ------------------------------------------------------------- params
    def init(self, rng) -> T.Params:
        return T.init_params(self.cfg, rng)

    def init_shapes(self, rng=None) -> Any:
        return jax.eval_shape(lambda: T.init_params(
            self.cfg, jax.random.PRNGKey(0)))

    # ------------------------------------------------------------ training
    def loss(self, params, batch, *, remat: str = "none",
             capacity_factor=None):
        return T.loss_fn(self.cfg, params, batch, remat=remat,
                         capacity_factor=capacity_factor)

    def forward(self, params, tokens, frontend=None, *, remat="none"):
        return T.forward(self.cfg, params, tokens, frontend, remat=remat)

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int) -> T.Cache:
        return T.init_cache(self.cfg, batch, max_len)

    def cache_shapes(self, batch: int, max_len: int) -> Any:
        return jax.eval_shape(partial(T.init_cache, self.cfg, batch, max_len))

    def prefill(self, params, tokens, cache, frontend=None, *,
                capacity_factor=None):
        return T.prefill(self.cfg, params, tokens, cache, frontend,
                         capacity_factor=capacity_factor)

    def decode_step(self, params, token, cache, *, capacity_factor=None):
        return T.decode_step(self.cfg, params, token, cache,
                             capacity_factor=capacity_factor)

    # ------------------------------------------------------------- helpers
    def frontend_shape(self, batch: int) -> Optional[Tuple[int, ...]]:
        cfg = self.cfg
        if cfg.frontend == "none" or not cfg.frontend_seq:
            return None
        return (batch, cfg.frontend_seq, cfg.d_model)


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
