"""Unified Stage-2 training engine (paper §III-B / §IV-D adaptation).

Everything that trains or fine-tunes the signature model goes through
one object: `Stage2Engine` wraps the distributed `Trainer` (microbatch
accumulation, sharding, checkpoint/restart, preemption) with the
stage-2 triplet + CPI + consistency loss over ROW-ID batches — each
step ships only integer ids, frequencies, and masks from the host; the
(B, N, bbe_dim) anchor/positive/negative gathers happen on-device
inside the jitted train step against one uploaded BBE matrix
(`stage2_loss_from_rows`, the training twin of the pipeline's
device-side inference batching).

The attention backend is selectable per engine: impl="pallas" runs the
fused set-attention kernel in BOTH directions (its custom VJP), compiled
on a TPU and interpreted elsewhere; "xla" runs the jnp reference.

`triplet_row_batch` assembles a training batch from already-selected
anchor/positive/negative intervals via the same `batch_set_ids` sort
the inference path uses — no per-interval host loops anywhere.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.config import TrainConfig
from repro.core.pipeline import BBEIndex, batch_set_ids
from repro.core.signature import SignatureConfig, stage2_loss_from_rows
from repro.kernels import check_backend
from repro.models.set_transformer import ATTENTION_BACKENDS
from repro.train.trainer import Trainer


def triplet_row_batch(sets: Dict[str, Sequence], cpis, index: BBEIndex,
                      max_set: int) -> Dict[str, Any]:
    """sets: {"anchor"|"positive"|"negative": [Interval] × B}; cpis: (B,)
    ground-truth CPI of the anchors. One vectorized `batch_set_ids` pass
    per role — the batch carries row ids into `BBEIndex.ext`, never the
    BBE payload."""
    out: Dict[str, Any] = {}
    for key in ("anchor", "positive", "negative"):
        rows, freqs, mask = batch_set_ids(sets[key], index, max_set)
        out[key] = {"rows": jnp.asarray(rows), "freqs": jnp.asarray(freqs),
                    "mask": jnp.asarray(mask)}
    out["cpi"] = jnp.asarray(np.asarray(cpis), jnp.float32)
    return out


class Stage2Engine:
    """Trainer-backed Stage-2 training over row-id triplet batches.

    matrix: (V+1, bbe_dim) BBE matrix with the zero sentinel row
    appended (`BBEIndex.ext`); uploaded once and closed over by the
    jitted train step. batch_fn(step) must return `triplet_row_batch`
    output — deterministic in `step` so checkpoint restarts replay the
    exact stream (the Trainer contract)."""

    def __init__(self, sig_cfg: SignatureConfig, params, param_specs,
                 matrix, cfg: TrainConfig, *, impl: str = "xla",
                 mesh=None, rules: Optional[Dict] = None,
                 donate: bool = False):
        self.sig_cfg = sig_cfg
        self.impl = check_backend("impl", impl, ATTENTION_BACKENDS)
        self.matrix = jnp.asarray(matrix)

        def loss_fn(p, batch):
            return stage2_loss_from_rows(p, sig_cfg, self.matrix, batch,
                                         impl=impl)

        # donate=False by default: engine callers (lab fine-tuning, §IV-D
        # sweeps) keep using the params tree they passed in — on TPU/GPU
        # the Trainer's donated first step would delete those buffers out
        # from under them. Flip on for throwaway params at pod scale.
        self.trainer = Trainer(loss_fn, params, param_specs, cfg,
                               mesh=mesh, rules=rules, donate=donate)

    # thin passthroughs — the Trainer owns state, checkpoints, preemption
    @property
    def params(self):
        return self.trainer.state.params

    @property
    def step_count(self) -> int:
        return self.trainer.state.step

    def step(self, batch) -> Dict[str, float]:
        return self.trainer.step(batch)

    def fit(self, batch_fn: Callable[[int], Any], num_steps: int,
            log_every: int = 10) -> Dict[str, float]:
        return self.trainer.fit(batch_fn, num_steps, log_every)

    def restore(self) -> bool:
        return self.trainer.restore()

    def maybe_checkpoint(self, force: bool = False):
        return self.trainer.maybe_checkpoint(force)

    def install_preemption_handler(self):
        self.trainer.install_preemption_handler()
