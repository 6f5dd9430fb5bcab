"""Content-hashed compile cache: schedule a Program once, run it forever.

Fused execution pays a host-side compile step per program —
:func:`repro.compile.schedule.build_schedule` levels the op stream and
groups each level's dispatches.  The workloads that matter repeat the
*same* program many times (serve ``heal_params`` votes every epoch,
sweep chunks share one chunk shape, ``pud.arith`` executors re-run a
traced adder per batch), so :class:`CompileCache` memoizes schedules by
program *content*: a SHA-256 over every op's semantic fields — kind,
arity, activation count, row addresses — deliberately excluding the
provenance ``tag``, which executors never read.  Two sweep chunks whose
ops differ only in point-index tags therefore share one schedule.

A :class:`~repro.compile.schedule.Schedule` is a pure function of that
content (frozen dataclasses, no backend state), so one cache can be
shared across sessions — the sweep runner shares a process-wide cache
across its per-chunk sessions, and the serve layer's session pool
shares one across concurrent request batches.  Lookups are serialized
by a lock (build included), so N concurrent submissions of one program
shape are exactly 1 miss + N-1 hits — never N racing builds.
``stats`` records hits/misses; the serve layer's SLO snapshots report
the hit rate.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Optional, TYPE_CHECKING

from repro.compile.schedule import Schedule, build_schedule
from repro.pud.isa import Program

if TYPE_CHECKING:  # pragma: no cover - import cycle guard for hints only
    from repro.analyze.cert import Certificate


def program_key(program: Program) -> str:
    """Content hash of a Program's semantic fields (tags excluded); a
    :class:`~repro.pud.isa.FrozenProgram` hashes once and keeps it."""
    return program.content_key()


@dataclasses.dataclass
class CacheStats:
    """Hit/miss counters, comparable across snapshots for windowing."""

    hits: int = 0
    misses: int = 0

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def snapshot(self) -> "CacheStats":
        return dataclasses.replace(self)

    def delta(self, earlier: "CacheStats") -> "CacheStats":
        """Stats accumulated since an ``earlier`` :meth:`snapshot`."""
        return CacheStats(hits=self.hits - earlier.hits,
                          misses=self.misses - earlier.misses)


class CompileCache:
    """LRU cache: ``program_key`` -> built :class:`Schedule`.

    A second LRU store under the same keys holds analysis
    :class:`~repro.analyze.cert.Certificate` records
    (:meth:`certificate_for`, ``certificate_stats``) so a repeated
    program certifies once and is a pure lookup afterwards.
    """

    def __init__(self, maxsize: int = 128):
        self.maxsize = maxsize
        self.stats = CacheStats()
        self.certificate_stats = CacheStats()
        self._entries: collections.OrderedDict[str, Schedule] = \
            collections.OrderedDict()
        self._certificates: "collections.OrderedDict[str, Certificate]" = \
            collections.OrderedDict()
        self._lock = threading.RLock()

    def __len__(self) -> int:
        return len(self._entries)

    def schedule_for(self, program: Program,
                     key: Optional[str] = None) -> Schedule:
        """The program's schedule — cached, or built and admitted.

        Pass a precomputed ``key`` (from :func:`program_key`) to skip
        re-hashing when the caller already derived it.  Thread-safe:
        the first caller for a key builds under the lock, concurrent
        callers for the same key wait and hit.
        """
        key = key or program_key(program)
        with self._lock:
            sched = self._entries.get(key)
            if sched is not None:
                self._entries.move_to_end(key)
                self.stats.hits += 1
                return sched
            self.stats.misses += 1
            sched = build_schedule(program)
            self._entries[key] = sched
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
            return sched

    def certificate_for(self, program: Program, key: Optional[str] = None,
                        sched: Optional[Schedule] = None) -> "Certificate":
        """The program's analysis :class:`~repro.analyze.cert.Certificate`.

        Cached under the same content key as schedules, with a second
        stats window (``certificate_stats``): a *hit* means the artifact
        was admitted analyzed and zero re-analysis happened — the
        property ``python -m repro.analyze --cache-check`` asserts.
        Raises :class:`~repro.analyze.cert.CertificationError` on any
        error finding — a program that fails certification is never
        admitted.
        """
        from repro.analyze.cert import certify

        key = key or program_key(program)
        with self._lock:
            cert = self._certificates.get(key)
            if cert is not None:
                self._certificates.move_to_end(key)
                self.certificate_stats.hits += 1
                return cert
            self.certificate_stats.misses += 1
            if sched is None:
                sched = self.schedule_for(program, key=key)
            cert = certify(program, sched=sched, key=key,
                           where=f"program {key[:12]}")
            self._certificates[key] = cert
            while len(self._certificates) > self.maxsize:
                self._certificates.popitem(last=False)
            return cert
