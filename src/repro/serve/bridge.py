"""Live-update bridge: flood inbox -> resident serving params (DESIGN.md §10).

A serving node holds a full replica of θ and subscribes to the same
SeedFlood overlay the trainers flood over.  Each step's
:class:`~repro.core.transport.FloodInbox` row for the node is buffered
here; at the next decode-step boundary the whole buffer folds into θ in
one jitted dispatch through :func:`repro.core.subcge.apply_messages_epoch`
— the epoch-grouped fold, so messages whose sender step crosses a
τ-refresh boundary are applied under the SENDER's subspace (PR 2's rule).
Because an update is (seed, coef, step) triples, folding K messages costs
one r×r scatter + one U A Vᵀ per weight — no tensors ever ship, which is
what makes fine-tune-while-serve cheap under SeedFlood.

Byte accounting stays in the Transport layer (SF005): the bridge only ever
consumes inbox rows the transport already charged to its CommLedger.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import subcge
from repro.core.messages import pad_pow2
from repro.core.subcge import SubCGEConfig
from repro.models import params as plib
from repro.models import transformer as tf

#: Padding triple for partially filled fold batches: coef 0.0 is an exact
#: no-op on every leaf kind and step -1 matches no epoch slot.
_PAD = (np.uint32(0), np.float32(0.0), np.int32(-1))


class LiveUpdateBridge:
    """Buffers SubCGE flood messages for one serving node and folds them."""

    def __init__(self, arch_cfg, scfg: SubCGEConfig, global_seed: int,
                 node: int):
        self.meta = plib.subcge_meta(tf.arch_spec(arch_cfg))
        self.scfg = scfg
        self.global_seed = global_seed
        self.node = node
        self._seeds: list[int] = []
        self._coefs: list[float] = []
        self._steps: list[int] = []
        self._fold_fns: dict[tuple[int, int], Any] = {}
        self.messages_folded = 0
        self.n_folds = 0

    # -- ingest ---------------------------------------------------------------

    def ingest(self, inbox) -> int:
        """Buffer this node's row of a FloodInbox; returns messages taken."""
        return self.ingest_arrays(inbox.seeds[self.node],
                                  inbox.coefs[self.node],
                                  inbox.steps[self.node])

    def ingest_arrays(self, seeds, coefs, steps) -> int:
        seeds = np.asarray(seeds).reshape(-1)
        coefs = np.asarray(coefs).reshape(-1)
        steps = np.asarray(steps).reshape(-1)
        live = steps >= 0                       # step -1 marks payload padding
        self._seeds.extend(np.uint32(seeds[live]).tolist())
        self._coefs.extend(np.float32(coefs[live]).tolist())
        self._steps.extend(np.int32(steps[live]).tolist())
        return int(live.sum())

    @property
    def pending(self) -> int:
        return len(self._seeds)

    # -- fold -----------------------------------------------------------------

    def _fold_fn(self, K: int, E: int):
        fn = self._fold_fns.get((K, E))
        if fn is None:
            def fold(params, seeds, coefs, steps, epochs):
                return subcge.apply_messages_epoch(
                    params, self.meta, self.scfg, self.global_seed,
                    seeds, coefs, steps, epochs)
            fn = jax.jit(fold)
            self._fold_fns[(K, E)] = fn
        return fn

    def fold(self, params):
        """Apply every buffered message to ``params`` (one jitted dispatch,
        pow2-padded so trace count stays bounded) and clear the buffer."""
        n = self.pending
        if n == 0:
            return params
        K = pad_pow2(n, minimum=1)
        seeds = np.full((K,), _PAD[0], np.uint32)
        coefs = np.full((K,), _PAD[1], np.float32)
        steps = np.full((K,), _PAD[2], np.int32)
        seeds[:n] = self._seeds
        coefs[:n] = self._coefs
        steps[:n] = self._steps
        epochs = subcge.epoch_slots(steps, self.scfg)
        fn = self._fold_fn(K, int(epochs.shape[0]))
        params = fn(params, jnp.asarray(seeds), jnp.asarray(coefs),
                    jnp.asarray(steps), jnp.asarray(epochs))
        self._seeds.clear()
        self._coefs.clear()
        self._steps.clear()
        self.messages_folded += n
        self.n_folds += 1
        return params

    def stats(self) -> dict:
        return {"messages_folded": self.messages_folded,
                "n_folds": self.n_folds, "pending": self.pending,
                "fold_programs": len(self._fold_fns)}
