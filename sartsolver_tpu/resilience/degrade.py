"""Adaptive OOM degradation: a batch-halving ladder on dispatch failure.

A dispatch that dies with the runtime's ``RESOURCE_EXHAUSTED`` is almost
never a reason to lose frames: the same frames usually solve at a
smaller frame-group size (half the measurement/solution batch on
device). The CLI's grouped frame loops consult a :class:`GroupSizeLadder`
around every dispatch:

- an OOM **halves** the current group size and re-solves the *same*
  frames at the reduced size — no frame is skipped, no row reordered
  (the chain loop's warm carry is untouched: the failed dispatch never
  updated it);
- the reduction **sticks** for the rest of the run (the memory did not
  come back; re-probing the old size would OOM every group) and is
  reported in the end-of-run resilience summary;
- at group size 1 the ladder is exhausted and the failure falls through
  to the existing per-frame isolation (a FRAME_FAILED row, or an abort
  under ``--fail_fast``).

The ladder is pure host-side control flow: with no OOM the dispatched
programs are exactly the ones the undegraded run compiles, and with the
layer "disabled" (nothing ever trips) the traced programs are
byte-identical — pinned by the ``guarded_dispatch`` compile-audit entry
below, whose golden signature must equal ``sharded_batch``'s.

Deterministic testing: the ``oom`` fault kind
(``SART_FAULT=solve.dispatch:oom:1:2``) raises
:class:`~sartsolver_tpu.resilience.faults.InjectedOOM`, whose message
carries the same ``RESOURCE_EXHAUSTED`` marker XLA uses, so
:func:`is_resource_exhausted` matches injected and real OOMs by one rule.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from sartsolver_tpu.resilience.faults import InjectedOOM

# Substrings marking a device allocation failure in the runtime's error
# text. XLA raises "RESOURCE_EXHAUSTED: Out of memory while trying to
# allocate ..." (jaxlib XlaRuntimeError); the lowercase "out of memory"
# alternative catches allocator messages that drop the status prefix.
_OOM_MARKERS = ("resource_exhausted", "out of memory")


def is_resource_exhausted(err: BaseException) -> bool:
    """True when ``err`` is a device out-of-memory — injected or real."""
    if isinstance(err, InjectedOOM):
        return True
    text = str(err).lower()
    return any(marker in text for marker in _OOM_MARKERS)


class GroupSizeLadder:
    """Current frame-group size plus the halving history.

    ``on_event`` (optional) receives one human-readable line per halving
    — the CLI wires it to the run summary and stderr.
    """

    def __init__(
        self,
        size: int,
        on_event: Optional[Callable[[str], None]] = None,
    ):
        if size < 1:
            raise ValueError("Group size must be positive.")
        self.initial = int(size)
        self.size = int(size)
        self.events: List[Tuple[int, int]] = []  # (from, to) per halving
        self._on_event = on_event
        # telemetry (obs/metrics.py): the current ladder level as a gauge
        # plus one counter tick per halving — host-side bookkeeping only
        from sartsolver_tpu.obs import metrics as obs_metrics

        registry = obs_metrics.get_registry()
        self._size_gauge = registry.gauge("frame_group_size")
        self._oom_counter = registry.counter("oom_degradations_total")
        self._size_gauge.set(self.size)

    @property
    def degraded(self) -> bool:
        return self.size != self.initial

    def note_oom(self, err: BaseException) -> bool:
        """Record an OOM at the current size. Returns True when the
        ladder halved (caller re-dispatches the same frames at
        ``self.size``), False when already at 1 (exhausted — fall through
        to per-frame isolation)."""
        if self.size <= 1:
            return False
        new = self.size // 2
        self.events.append((self.size, new))
        if self._on_event is not None:
            self._on_event(
                f"device OOM at frame-group size {self.size} "
                f"({type(err).__name__}); re-solving the same frames at "
                f"{new} — the reduction sticks for the rest of the run"
            )
        self.size = new
        self._size_gauge.set(new)
        self._oom_counter.inc()
        return True

    def summary(self) -> Optional[str]:
        """One summary line for the run accounting, or None when the
        ladder never tripped."""
        if not self.events:
            return None
        path = " -> ".join(
            [str(self.events[0][0])] + [str(new) for _, new in self.events]
        )
        return (
            f"oom degradation: frame-group size {path} "
            f"({len(self.events)} event(s); reduced size kept for the "
            "rest of the run)"
        )


def dispatch_guarded(
    dispatch: Callable[[], object],
    *,
    ladder: Optional[GroupSizeLadder] = None,
):
    """Run one dispatch under the availability wrappers: a dispatch-phase
    beacon for the hang watchdog, and OOM classification for the ladder.

    Returns ``(result, None)`` on success and ``(None, err)`` after an
    OOM that halved the ladder (the caller re-stacks the same frames at
    ``ladder.size`` and dispatches again). Every other exception — and an
    OOM with the ladder exhausted or absent — propagates unchanged, so
    the caller's isolation semantics are exactly the unwrapped ones.
    """
    from sartsolver_tpu.obs import trace as obs_trace
    from sartsolver_tpu.resilience import watchdog

    watchdog.beacon(watchdog.PHASE_DISPATCH)
    try:
        with obs_trace.span("group.dispatch"):
            return dispatch(), None
    except Exception as err:
        if (
            ladder is not None
            and is_resource_exhausted(err)
            and ladder.note_oom(err)
        ):
            return None, err
        raise


# --------------------------------------------------------------------------
# compile-audit self-registration (analysis/registry.py): the dispatch
# path the CLI actually runs is now wrapped by the availability layer
# (beacon + ladder above). The wrappers are host-only by design; this
# entry lowers the sharded batched solve THROUGH dispatch_guarded with a
# live (untripped) ladder and a running beacon, and its golden signature
# is asserted byte-equal to the unwrapped `sharded_batch` entry's
# (tests/test_availability.py) — the machine-checked form of "with the
# layer disabled the traced programs are identical".

from sartsolver_tpu.analysis.registry import (  # noqa: E402
    AUDIT_P as _AUDIT_P,
    AUDIT_V as _AUDIT_V,
    register_audit_entry as _register_audit_entry,
)

_AUDIT_SHARDS = 2


@_register_audit_entry(
    "guarded_dispatch",
    description="sharded batched solve dispatched through the "
                "availability layer (watchdog beacon + OOM ladder armed, "
                "nothing tripped); golden must equal sharded_batch's",
    loop_copy_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_convert_threshold=(_AUDIT_P // _AUDIT_SHARDS) * _AUDIT_V,
    loop_collective_budget={
        "all-reduce": 2, "all-gather": 0, "all-to-all": 0,
        "collective-permute": 0,
    },
    min_devices=_AUDIT_SHARDS,
)
def _audit_guarded_dispatch():
    from sartsolver_tpu.parallel.sharded import _audit_sharded_batch

    ladder = GroupSizeLadder(2)
    lowered, err = dispatch_guarded(_audit_sharded_batch, ladder=ladder)
    assert err is None and not ladder.degraded  # nothing tripped
    return lowered


__all__ = [
    "GroupSizeLadder",
    "dispatch_guarded",
    "is_resource_exhausted",
]
