"""General generator for ``DramSession.elementwise``: one caller in a
closed loop.

The configuration gives the session (backend and execution context) and
the operation.  The traffic file gives:

``lanes``
    ``uint32`` lanes per call.
``pool``
    operand pairs made on the device from the seed in one jitted call;
    the caller cycles through them.
``warm_calls``
    untimed calls made in set-up, after which every program the window
    runs is compiled.
``check_sample``
    answers kept for the comparison, drawn from the seed.

Each call waits for its result (``block_until_ready``) before the next.
"""

from __future__ import annotations

import time

import numpy as np

import reference
from harness import Record, Reservoir, Window, annotate, annotate_session

#: The reference and its control for each operation.
REFERENCE = {"add": (reference.add, reference.control_add)}

#: Device bytes per lane a binary operation needs at the least: two
#: ``uint32`` operands read, one ``uint32`` result written.
BYTES_PER_LANE = 12


def required_bytes(lanes: int) -> int:
    return BYTES_PER_LANE * lanes


def make_operands(seed: int, pool: int, lanes: int):
    """``pool`` operand pairs of ``lanes`` words, in one jitted call."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(int(np.random.SeedSequence(seed).generate_state(
        1)[0]))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * pool)
        return tuple(jax.random.bits(k, (lanes,), jnp.uint32) for k in keys)

    flat = jax.block_until_ready(make(key))
    return [(flat[2 * j], flat[2 * j + 1]) for j in range(pool)]


class ElementwiseRun:
    def __init__(self, config: dict, traffic: dict, seed: int, spans: bool):
        from repro.backends import ExecutionContext
        from repro.session import DramSession

        self.op = config["op"]
        if self.op not in REFERENCE:
            raise ValueError(f"no reference for operation {self.op!r}")
        self.traffic = traffic
        self.lanes = traffic["lanes"]
        self.session = DramSession(config["backend"],
                                   ExecutionContext(**config["ctx"]))
        if spans:
            annotate(self.session, "elementwise", "session.elementwise")
            annotate_session(self.session)
        self.pairs = make_operands(seed, traffic["pool"], self.lanes)
        self.sample = Reservoir(traffic["check_sample"], seed + 2)

    def _call(self, j: int):
        a, b = self.pairs[j % len(self.pairs)]
        out, _ = self.session.elementwise(self.op, a, b)
        return out.block_until_ready()

    def warm(self) -> None:
        for j in range(self.traffic["warm_calls"]):
            self._call(j)

    def window(self, seconds: float) -> Window:
        records, j = [], 0
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            t_sub = time.perf_counter()
            out = self._call(j)
            records.append(Record(self.op, t_sub, time.perf_counter(),
                                  required_bytes=required_bytes(self.lanes),
                                  elements=self.lanes))
            self.sample.add((j % len(self.pairs), out))
            j += 1
        return Window(records, t0, time.perf_counter())

    def release(self) -> None:
        """Answers and operands to the host; the session goes."""
        self.sample.items = [(j, np.asarray(out))
                             for j, out in self.sample.items]
        self.pairs = [(np.asarray(a), np.asarray(b)) for a, b in self.pairs]
        self.session = None

    def install_control(self) -> None:
        """Answer every call from the reference's control."""
        import jax.numpy as jnp

        control = REFERENCE[self.op][1]

        def elementwise(op, a, b):
            return jnp.asarray(control(np.asarray(a), np.asarray(b))), None

        self.session.elementwise = elementwise

    def checks(self) -> dict:
        ref = REFERENCE[self.op][0]
        want = {}
        wrong = 0
        for j, out in self.sample.items:
            if j not in want:
                want[j] = ref(*self.pairs[j])
            if out.shape != want[j].shape:
                wrong += self.lanes
            else:
                wrong += int(np.count_nonzero(out != want[j]))
        return {"wrong_lanes": {"value": wrong, "limit": 0}}


def build(config: dict, traffic: dict, seed: int, *,
          spans: bool) -> ElementwiseRun:
    return ElementwiseRun(config, traffic, seed, spans)
