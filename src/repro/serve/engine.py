"""Serving engine: continuous batching over prefill/decode steps.

A fixed-width decode batch of ``slots``; finished sequences free their slot
and queued requests are prefilled into it (continuous batching a la Orca /
vLLM).  Greedy or temperature sampling.  All model math lives in
repro.models.model; the engine is pure scheduling.

PUD hooks: the engine's integrity work (replica vote-healing and
bit-level verification) runs through a :class:`~repro.serve.service.
PudService` — the engine is a thin *client*: it installs its replicas
on the device, scrubs them through the service
(:meth:`~repro.serve.service.PudService.scrub`) and submits typed
:class:`~repro.serve.queue.IntegrityRequest` work, so engine votes share
the service's session pool, schedule cache, queue and SLO accounting
with every other tenant.  The offload planner's verdict (where the vote
*would* run on PUD-capable memory; advisory on TPU-only deployments) is
kept for each heal.

Integrity votes must be error-free, so healing on a non-ideal
:class:`~repro.backends.context.ExecutionContext` (a stochastic backend
can corrupt the very bits it claims to heal) emits
:class:`IntegrityContextWarning` — or raises
:class:`IntegrityContextError` under ``strict_integrity=True``.
Non-ideal contexts are for fidelity studies, never serving deployments.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import ExecutionContext
from repro.configs.base import ModelConfig
from repro.core import calibration as cal
from repro.models import model as M
from repro.serve import scrub
from repro.serve.queue import IntegrityRequest, ServeError
from repro.serve.service import PudService, ServiceConfig


#: Widest replica vote an engine-owned service is sized for: MAJ9, the
#: widest majority the paper's chips perform (Table 1).
MAX_HEAL_REPLICAS = max(cal.MAJX_MAX_X.values())


class IntegrityContextError(ServeError):
    """heal_params refused to run on a non-ideal context (strict mode)."""


class IntegrityContextWarning(UserWarning):
    """heal_params is running on a non-ideal (stochastic) context."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    """Single-slot-group engine (one jitted decode fn, batch = n slots)."""

    def __init__(self, params, cfg: ModelConfig, max_seq: int = 256,
                 greedy: bool = True, seed: int = 0,
                 pud_backend: str = "pallas",
                 pud_ctx: Optional[ExecutionContext] = None,
                 pud_service: Optional[PudService] = None,
                 strict_integrity: bool = False,
                 tenant: str = "engine"):
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        # Integrity work runs through a PudService; pass a shared
        # ``pud_service`` to pool votes with other engines/tenants, or
        # let the engine own a single-session service.  The service
        # defaults to an ideal context (see module docstring).  An owned
        # service's row budget fits a scrub tile of up to
        # MAX_HEAL_REPLICAS replicas and a verify of the whole packed
        # params, whatever the model size.
        layout = scrub.layout_of(params)
        self.service = pud_service or PudService(ServiceConfig(
            backend=pud_backend,
            ctx=pud_ctx or ExecutionContext(ideal=True), pool_size=1,
            tenant_rows=max(ServiceConfig.tenant_rows,
                            (MAX_HEAL_REPLICAS + 1) * layout.tile_rows,
                            2 * layout.rows)))
        self.strict_integrity = strict_integrity
        self.tenant = tenant
        #: Compat: the first pooled session still answers the whole
        #: Backend surface (examples introspect ``engine.pud.ctx`` etc.).
        self.pud = self.service.sessions[0]
        self.pud_decisions: list = []
        self._decode = jax.jit(
            lambda p, t, c: M.decode(p, t, c, cfg))
        self._prefill = jax.jit(
            lambda p, b: M.prefill(p, b, cfg, max_seq))

    # ------------------------------------------------------------ PUD hooks
    def _check_integrity_ctx(self) -> None:
        """Enforce the ideal-context-by-default healing rule.

        Warns on a non-ideal context; raises under ``strict_integrity``.
        """
        if self.service.ctx.ideal:
            return
        msg = (f"heal_params is running on a non-ideal ExecutionContext "
               f"(mfr={self.service.ctx.mfr!r}, ideal=False): a "
               f"stochastic backend can corrupt the very bits it claims "
               f"to heal. Use ExecutionContext(ideal=True) for serving; "
               f"non-ideal contexts are for fidelity studies only.")
        if self.strict_integrity:
            raise IntegrityContextError(msg)
        warnings.warn(msg, IntegrityContextWarning, stacklevel=3)

    def heal_params(self, replicas: Sequence) -> int:
        """Majority-vote parameter replicas through the PUD service.

        ``replicas``: >= 3 (odd) pytrees with the engine's param
        structure.  Installs the healed params and returns the number
        of bits corrected in ``replicas[0]``.

        The engine is a thin client: the replicas are packed onto the
        device as a :class:`~repro.serve.scrub.ReplicaSet`, scrubbed
        tile by tile through the service (one schedule-cached tile
        Program for every tile, one batched MAJX dispatch each on the
        ``pallas`` backend), and unpacked once.  The offload planner's
        verdict for the tile vote is appended to ``self.pud_decisions``
        (advisory: where the vote would run on PUD-capable memory).
        """
        self._check_integrity_ctx()
        rs = self.service.install_replica_trees(replicas,
                                                tenant=self.tenant)
        result = self.service.scrub(rs)
        self.params = self.service.live(rs)
        self.pud_decisions.append(scrub.plan(self.pud, rs))
        return result.corrected[0]

    def verify_params(self, reference) -> float:
        """Bit-level success rate of live params vs a reference pytree.

        One typed :class:`~repro.serve.queue.IntegrityRequest` through
        the service over both trees packed on the device (the layout's
        zero padding matches on both sides, so the packed comparison
        equals the per-leaf one; the rate is normalized by the real
        parameter bits, not the padding).
        """
        layout = scrub.layout_of(self.params)
        [result] = self.service.serve([IntegrityRequest(
            live=scrub.pack(self.params, layout),
            reference=scrub.pack(reference, layout), tenant=self.tenant)])
        return 1.0 - result.mismatch_bits / max(layout.words * 32, 1)

    # ------------------------------------------------------------ serving
    def _sample(self, logits) -> np.ndarray:
        lg = np.asarray(logits.astype(jnp.float32))
        if self.cfg.family == "audio":
            return lg.argmax(-1)[:, 0]     # (B, CB)
        return lg.argmax(-1)[:, 0]         # (B,)

    def generate(self, requests: list[Request]) -> list[Request]:
        """Serve a list of requests with continuous batching."""
        queue = list(requests)
        active: list[Request] = []
        cache = None
        while queue or active:
            # (re)fill the batch: group requests with equal prompt lengths
            # into one prefill (static-shape jit); simple policy: batch all
            # queued requests of the most common length.
            if not active and queue:
                lens = [len(r.prompt) for r in queue]
                target = max(set(lens), key=lens.count)
                batch_reqs = [r for r in queue if len(r.prompt) == target]
                queue = [r for r in queue if len(r.prompt) != target]
                toks = jnp.asarray(np.stack([r.prompt for r in batch_reqs]))
                logits, cache = self._prefill(self.params, {"tokens": toks})
                first = self._sample(logits)
                for i, r in enumerate(batch_reqs):
                    r.out_tokens.append(first[i])
                active = batch_reqs
            # decode until every active request finishes
            while active and not all(r.done for r in active):
                last = np.stack([r.out_tokens[-1] for r in active])
                if self.cfg.family == "audio":
                    toks = jnp.asarray(last.reshape(len(active), 1, -1))
                else:
                    toks = jnp.asarray(last.reshape(len(active), 1))
                logits, cache = self._decode(self.params, toks, cache)
                nxt = self._sample(logits)
                for i, r in enumerate(active):
                    if r.done:
                        continue
                    r.out_tokens.append(nxt[i])
                    tok_scalar = (int(np.asarray(nxt[i]).flat[0])
                                  if np.ndim(nxt[i]) else int(nxt[i]))
                    if (len(r.out_tokens) >= r.max_new_tokens
                            or (r.eos_id is not None
                                and tok_scalar == r.eos_id)):
                        r.done = True
            active = []
            cache = None
        return requests
