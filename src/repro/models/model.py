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
             use_kernel: bool = False, capacity_factor=None):
        return T.loss_fn(self.cfg, params, batch, remat=remat,
                         use_kernel=use_kernel,
                         capacity_factor=capacity_factor)

    def forward(self, params, tokens, frontend=None, *, remat="none",
                use_kernel: bool = False):
        return T.forward(self.cfg, params, tokens, frontend, remat=remat,
                         use_kernel=use_kernel)

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int) -> T.Cache:
        return T.init_cache(self.cfg, batch, max_len)

    def cache_shapes(self, batch: int, max_len: int) -> Any:
        return jax.eval_shape(partial(T.init_cache, self.cfg, batch, max_len))

    def prefill(self, params, tokens, cache, frontend=None, *,
                use_kernel: bool = False, capacity_factor=None):
        return T.prefill(self.cfg, params, tokens, cache, frontend,
                         use_kernel=use_kernel, capacity_factor=capacity_factor)

    @property
    def decode_carries_cache(self) -> bool:
        """The decode writes its K/V rows into the stacked cache in place."""
        return T.decode_carries_cache(self.cfg)

    def decode_runs_kernel(self, cache, platform: str) -> bool:
        """A one-token decode over ``cache`` on one device of ``platform``
        runs the decode-attention kernel."""
        return T.decode_runs_kernel(self.cfg, cache, platform)

    def decode_step(self, params, token, cache, *, use_kernel: bool = False,
                    capacity_factor=None):
        return T.decode_step(self.cfg, params, token, cache,
                             use_kernel=use_kernel,
                             capacity_factor=capacity_factor)

    # ------------------------------------------------------------- helpers
    def frontend_shape(self, batch: int) -> Optional[Tuple[int, ...]]:
        cfg = self.cfg
        if cfg.frontend == "none" or not cfg.frontend_seq:
            return None
        return (batch, cfg.frontend_seq, cfg.d_model)


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
