"""Which of its two orders an iteration of ``_dreamer_main`` runs in.

With the replay ring on the device an iteration's device work is enqueued in
one sequence whatever the host does in between: player forward, ring add,
ring sample, batch staging, train step.  What the host can move is where its
two blocking calls stand against the sample and the train dispatch:

- ``env_overlap``: fetch the action and hand it to the envs *first*, then
  sample and dispatch the train step while the envs step.  The host's sample
  and dispatch hide behind the env step; the device has nothing queued from
  the moment the previous train step ends (the fetch returns then) until the
  next one is enqueued.  Right for a simulator slower than that host work.
- ``train_first``: sample and dispatch the train step *first*, then fetch the
  action and step the envs.  The device runs player, add, sample and train
  step back to back; the env step stands on the host's serial path.  Right
  for a simulator faster than the host's sample and dispatch.

Both give the same parameters, optimizer state and ring contents bit for bit
(the device's stream and the ``rng_key`` splits are the same), so the choice
is safe to make at run time, and :class:`LoopOrder` makes it on the one thing
that matters and that the loop can observe: its own iteration time.  The
break-even depends on the env's latency and on the model, and a simulator's
latency drifts with the scene, so it measures again now and then.
"""

from __future__ import annotations

import time
from statistics import median
from typing import Callable, Dict, List, Optional

ENV_OVERLAP = "env_overlap"
TRAIN_FIRST = "train_first"
ORDERS = (ENV_OVERLAP, TRAIN_FIRST)

# A measurement, in training iterations (those in which the player acted and a
# gradient step was dispatched): SETTLE of them pass in ``env_overlap`` after
# training starts, then BLOCKS blocks of BLOCK run in each order alternately,
# the first SKIP of a block untimed (they still carry the other order's
# queue), so the first decision falls at iteration 16 + 6 * 16 = 112; the next
# measurement starts PERIOD after a decision, which keeps the iterations run in
# the order that lost under half a percent (48 of 10,096).
SETTLE, BLOCK, BLOCKS, SKIP, PERIOD = 16, 16, 3, 2, 10_000


class LoopOrder:
    """The order of each iteration: measured in short alternating blocks,
    then kept, then measured again every ``PERIOD`` training iterations.

    The loop asks once an iteration, at its top: ``order = begin(player_acts,
    trained_iterations)``.  The time between two such calls is the wall time
    of the iteration between them; it is counted when that iteration could
    have run in either order (``player_acts``) and dispatched a gradient step
    (``trained_iterations`` grew), and is dropped for the first ``SKIP``
    counted iterations of a block.
    The order with the lower *median* is kept: an episode end, a metric flush
    or a checkpoint inside a block decides nothing.  A tie keeps
    ``env_overlap``.

    ``two_orders=False`` (the ring on the host: the add needs the fetched
    action, so the sample cannot precede the fetch; ``dry_run``) never
    measures and always answers ``env_overlap``, as does any iteration in
    which the player does not act (prefill).  ``force`` pins the order of
    every iteration that has two and never measures (tests).

    ``count(order)`` is called once an iteration and ``journal(**fields)``
    once a decision.
    """

    def __init__(
        self,
        two_orders: bool,
        *,
        force: Optional[str] = None,
        clock: Callable[[], float] = time.perf_counter,
        count: Optional[Callable[[str], None]] = None,
        journal: Optional[Callable[..., None]] = None,
    ):
        if force is not None and force not in ORDERS:
            raise ValueError(f"force must be one of {ORDERS}, got {force!r}")
        self._measures = bool(two_orders) and force is None
        self._two_orders = bool(two_orders)
        self._clock = clock
        self._count = count
        self._journal = journal
        self.kept = force if force is not None else ENV_OVERLAP
        self.decisions = 0
        self._counted = 0  # iterations that had two orders and trained
        self._measure_at = SETTLE  # ... of which this many pass before the next measurement
        self._schedule: List[str] = []  # the blocks still to run, the current one first
        self._in_block = 0
        self._samples: Dict[str, List[float]] = {}
        self._open: Optional[tuple] = None  # the open iteration: (its start, its order, had two orders, trained_iterations then)

    def begin(self, player_acts: bool, trained_iterations: int) -> str:
        """Close the iteration that ends here and answer the order of the one that starts."""
        has_two = self._two_orders and player_acts
        if self._measures:
            now = self._clock()
            if self._open is not None:
                since, order, counts, trained_before = self._open
                if counts and trained_iterations > trained_before:
                    self._close(order, now - since)
        order = (self._schedule[0] if self._schedule else self.kept) if has_two else ENV_OVERLAP
        if self._measures:
            self._open = (now, order, has_two, trained_iterations)
        if self._count is not None:
            self._count(order)
        return order

    def _close(self, order: str, seconds: float) -> None:
        self._counted += 1
        if not self._schedule:
            if self._counted >= self._measure_at:
                other = TRAIN_FIRST if self.kept == ENV_OVERLAP else ENV_OVERLAP
                self._schedule = [other, self.kept] * BLOCKS
                self._samples = {ENV_OVERLAP: [], TRAIN_FIRST: []}
                self._in_block = 0
            return
        self._in_block += 1
        if self._in_block > SKIP:
            self._samples[order].append(seconds)
        if self._in_block >= BLOCK:
            self._in_block = 0
            del self._schedule[0]
            if not self._schedule:
                self._decide()

    def _decide(self) -> None:
        medians = {order: median(self._samples[order]) for order in ORDERS}
        self.kept = TRAIN_FIRST if medians[TRAIN_FIRST] < medians[ENV_OVERLAP] else ENV_OVERLAP
        self.decisions += 1
        self._measure_at = self._counted + PERIOD
        if self._journal is not None:
            self._journal(
                kept=self.kept,
                decision=self.decisions,
                training_iterations=self._counted,
                samples=len(self._samples[self.kept]),
                **{f"{order}_median_ms": round(1e3 * medians[order], 4) for order in ORDERS},
            )
