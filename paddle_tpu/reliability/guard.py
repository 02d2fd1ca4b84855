"""Self-healing training: on-device numeric guards + skip/rollback
policies (ISSUE 9).

The repo survives any *process* failure (kill-anywhere resume, fleet
failover) but until this module the only response to a *numeric*
failure was a hard abort: ``FLAGS check_nan_inf`` host-synced the loss
every step and raised, loss spikes and exploding grad norms went
undetected, and inside a ``steps_per_loop=K`` scan one poisoned batch
silently corrupted params for K-1 more steps before the host ever saw
it. This module makes transient bad math a recoverable fault class
with the same seeded-replay discipline as :mod:`.faults`:

- **NumericGuard (device side)** — ``device_state`` / ``inspect`` /
  ``apply_mask`` / ``update_state`` are pure functions traced INTO the
  jitted train step: a finite-mask over the loss and every grad leaf,
  the global grad L2 norm, and loss-spike detection against an EMA
  carried in the donated device-state pytrees. Inside the fused
  ``lax.scan`` the param/opt-state/buffer update is masked per step
  with ``jnp.where`` so a tripped step becomes an EXACT no-op update
  (the carry passes through untouched) without breaking the
  one-dispatch property. Zero extra host syncs: verdicts come back as
  stacked device arrays and ride the same buffered drain as the lazy
  metrics.

- **GuardPolicy (host side)** — consumes drained verdicts and applies
  the response: ``skip`` (the device already no-op'd; count against a
  budget), ``rollback`` (:class:`GuardRollback` — ``Model.fit``
  restores the newest verified checkpoint via the manifest path and
  fast-forwards the DataLoader cursor past the offending range, with
  escalating stride on repeat trips), or ``abort``
  (:class:`GuardAbort`, a ``FloatingPointError`` carrying the
  per-tensor non-finite report from ``amp.debugging``, the offending
  step fingerprint, and a one-line deterministic replay command, plus
  a flight-recorder dump).

Exactness scope of **skip**: a run that skips step ``s`` is
bit-identical (params and loss stream) to a clean run over the same
stream with batch ``s`` removed, provided the per-step math does not
key on the global step index — constant learning rate and no
dropout/noise layers (per-step RNG keys and LR schedules fold in the
step index, which shifts by one after a skip). The poisoned-stream
chaos gate (``tools/chaos_soak.py --ci --train``) pins this at
``steps_per_loop`` in {1, 4}.

Determinism: the seeded fault sites ``data.poison`` (NaNs a host
batch before dispatch) and ``grad.nonfinite`` (a NaN multiplier on
the loss inside the jitted step — grads and loss go non-finite on
schedule without retracing) make every policy path replayable;
``faults.preview(site, N)`` is the schedule witness.

Disabled cost: ``Model.prepare`` leaves ``model._guard = None`` unless
armed (``numeric_guard=`` argument or the ``numeric_guard`` flag), and
the train paths check that one attribute — the compiled program
contains no guard ops at all (pinned by tests via the lowered HLO
text).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics as _obs
from ..observability import tracing as _trace

_ACTIONS_NONFINITE = ("skip", "rollback", "abort")
_ACTIONS_SPIKE = ("allow", "skip", "rollback", "abort")


def _guard_metrics():
    """guard_* instruments (docs/OBSERVABILITY.md). GradScaler's
    inf/nan skip feeds the same families so scaler skips and guard
    skips read on one dashboard."""
    reg = _obs.default_registry()
    return {
        "trips": reg.counter(
            "guard_trips_total",
            "numeric-guard detections by detector kind and policy "
            "action", label_names=("kind", "action")),
        "skipped": reg.counter(
            "guard_skipped_steps_total",
            "optimizer steps no-op'd (device-masked) by the numeric "
            "guard or the AMP GradScaler"),
        "rollbacks": reg.counter(
            "guard_rollbacks_total",
            "checkpoint rollbacks triggered by the numeric guard"),
        "grad_norm": reg.gauge(
            "train_grad_norm",
            "global grad L2 norm of the newest drained healthy step "
            "(guard-computed on device, read at drain boundaries)"),
    }


# ---------------------------------------------------------------------------
# device side — pure functions traced into the jitted train step
# ---------------------------------------------------------------------------


def device_state() -> Dict[str, jax.Array]:
    """The EMA carry: rides the donated device-state pytrees across
    the whole scan (and the checkpoint tree, so resume keeps the
    spike baseline)."""
    return {"ema": jnp.zeros([], jnp.float32),
            "n": jnp.zeros([], jnp.int32)}


def inspect(loss, grads, state, *, spike_factor: float,
            spike_margin: float, warmup_steps: int):
    """On-device verdict for one step: 0 healthy, 1 non-finite (loss
    or any grad leaf), 2 loss spike vs the EMA. Also returns the
    global grad L2 norm (f32) — NaN/Inf grads surface there too, but
    the finite mask is the authoritative bit (a finite-but-overflowing
    squared sum must not misclassify)."""
    loss = loss.astype(jnp.float32)
    finite = jnp.isfinite(loss)
    sq = jnp.zeros([], jnp.float32)
    for g in jax.tree_util.tree_leaves(grads):
        if not jnp.issubdtype(jnp.asarray(g).dtype, jnp.inexact):
            continue
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
    gnorm = jnp.sqrt(sq)
    warmed = state["n"] >= warmup_steps
    # ema + (factor-1)*|ema|, NOT ema*factor: identical for ema >= 0,
    # but a plain multiply INVERTS for negative-loss objectives (log-
    # likelihoods: ema=-10, factor 4 -> threshold -40, every normal
    # step "spikes") — the margin above baseline must scale with the
    # loss MAGNITUDE, whatever its sign
    thresh = state["ema"] + (spike_factor - 1.0) * jnp.abs(
        state["ema"]) + spike_margin
    spike = jnp.logical_and(warmed, loss > thresh)
    verdict = jnp.where(jnp.logical_not(finite), 1,
                        jnp.where(spike, 2, 0)).astype(jnp.int32)
    return verdict, gnorm


def apply_mask(verdict, mask_spikes: bool):
    """Should THIS step's update apply? Non-finite steps never do;
    spike steps are masked only when the policy responds to spikes
    (``mask_spikes`` is static at trace time — the policy is fixed at
    prepare())."""
    bad = verdict == 1
    if mask_spikes:
        bad = jnp.logical_or(bad, verdict == 2)
    return jnp.logical_not(bad)


def update_state(state, loss, applied, decay: float):
    """EMA update — only for applied, finite-loss steps, so a tripped
    step leaves the baseline untouched (exactly like the clean run
    that never saw the batch). The first applied loss seeds the EMA
    so warmup never compares against zero. ``decay`` is policy config,
    static at trace time."""
    loss = loss.astype(jnp.float32)
    upd = jnp.logical_and(applied, jnp.isfinite(loss))
    ema0 = jnp.where(state["n"] == 0, loss, state["ema"])
    ema = jnp.where(upd, decay * ema0 + (1.0 - decay) * loss,
                    state["ema"])
    return {"ema": ema, "n": state["n"] + upd.astype(jnp.int32)}


def mask_pytree(ok, new, old):
    """Per-leaf select: the whole update becomes an exact no-op when
    ``ok`` is False — params, optimizer moments/counters and buffers
    all keep their pre-step bits."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new, old)


# ---------------------------------------------------------------------------
# host side — the policy engine
# ---------------------------------------------------------------------------


class GuardRollback(RuntimeError):
    """Control-flow escalation: restore the newest verified checkpoint
    and fast-forward the loader cursor ``stride`` batches past the
    offending step. ``Model.fit`` catches this; anything else treating
    it as an error is correct too (manual train_batch loops without a
    checkpoint manager cannot roll back)."""

    def __init__(self, step: int, kind: str, stride: int):
        super().__init__(
            f"numeric guard rollback: {kind} at step {step} "
            f"(fast-forward stride {stride})")
        self.step = int(step)
        self.kind = kind
        self.stride = int(stride)


class GuardAbort(FloatingPointError):
    """Terminal verdict. Subclasses FloatingPointError so existing
    ``check_nan_inf`` catchers keep working; the message carries the
    per-tensor report, the step fingerprint and the replay command,
    and a flight-recorder dump is emitted before the raise."""

    def __init__(self, msg: str, step: int, kind: str):
        super().__init__(msg)
        self.step = int(step)
        self.kind = kind


class GuardPolicy:
    """Response policy over drained guard verdicts.

    - ``on_nonfinite``: ``"skip"`` (default) | ``"rollback"`` |
      ``"abort"``;
    - ``on_spike``: ``"allow"`` (default: record only — the update
      still applies) | ``"skip"`` | ``"rollback"`` | ``"abort"``;
    - ``budget``: total skipped steps tolerated before escalating to
      abort (skips past the budget mean the data or the math is not
      transiently bad);
    - ``max_rollbacks``: rollback attempts before escalating;
    - ``rollback_stride``: batches to fast-forward past the offending
      step on the first rollback — doubled on each repeat trip
      (1, 2, 4, ...) so a poisoned RANGE is eventually cleared;
    - spike detector shape: ``loss > ema + (spike_factor - 1) *
      |ema| + spike_margin`` once ``warmup_steps`` applied steps have
      fed the EMA (``ema_decay``) — equal to ``ema * spike_factor``
      for non-negative losses, and still "magnitude blowup above
      baseline" for negative-loss objectives.
    """

    def __init__(self, on_nonfinite: str = "skip",
                 on_spike: str = "allow", budget: int = 8,
                 max_rollbacks: int = 4, rollback_stride: int = 1,
                 spike_factor: float = 4.0, spike_margin: float = 0.0,
                 warmup_steps: int = 16, ema_decay: float = 0.98):
        if on_nonfinite not in _ACTIONS_NONFINITE:
            raise ValueError(
                f"on_nonfinite={on_nonfinite!r} not in "
                f"{_ACTIONS_NONFINITE}")
        if on_spike not in _ACTIONS_SPIKE:
            raise ValueError(
                f"on_spike={on_spike!r} not in {_ACTIONS_SPIKE}")
        self.on_nonfinite = on_nonfinite
        self.on_spike = on_spike
        self.budget = int(budget)
        self.max_rollbacks = int(max_rollbacks)
        self.rollback_stride = max(int(rollback_stride), 1)
        self.spike_factor = float(spike_factor)
        self.spike_margin = float(spike_margin)
        self.warmup_steps = int(warmup_steps)
        self.ema_decay = float(ema_decay)
        # host-side accounting (surfaced on /statusz)
        self.n_trips = 0
        self.n_skipped = 0
        self.n_rollbacks = 0
        self.n_allowed_spikes = 0
        self.last_trip_step: Optional[int] = None
        self.last_trip_kind: Optional[str] = None

    # -- trace-time hooks ----------------------------------------------------
    @property
    def mask_spikes(self) -> bool:
        """Static at trace time: whether the device no-ops spike
        steps (any spike response except "allow" must not train on
        the spiked batch — even abort, which the host only sees at
        the next drain)."""
        return self.on_spike != "allow"

    def device_state(self) -> Dict[str, jax.Array]:
        return device_state()

    def inspect(self, loss, grads, state):
        return inspect(loss, grads, state,
                       spike_factor=self.spike_factor,
                       spike_margin=self.spike_margin,
                       warmup_steps=self.warmup_steps)

    def update_state(self, state, loss, applied):
        return update_state(state, loss, applied, self.ema_decay)

    # -- the drain-boundary engine -------------------------------------------
    def process(self, verdicts, gnorms, losses, step0: int,
                model=None) -> None:
        """Apply the policy to one drained dispatch's verdicts
        (arrays of length K; ``step0`` is the dispatch's first global
        step). Called from the Model's buffered metric drain — ONE
        host sync per log boundary covers metrics, losses AND guard
        verdicts. Raises :class:`GuardRollback` / :class:`GuardAbort`
        per the policy; plain skips only update accounting (the
        device already no-op'd the update)."""
        verdicts = np.asarray(verdicts).reshape(-1)
        gnorms = np.asarray(gnorms).reshape(-1)
        losses = np.asarray(losses).reshape(-1)
        m = _guard_metrics()
        last_norm = None
        for i, v in enumerate(int(x) for x in verdicts):
            gstep = int(step0) + i
            if v == 0:
                if np.isfinite(gnorms[i]):
                    last_norm = float(gnorms[i])
                continue
            kind = "nonfinite" if v == 1 else "spike"
            action = self.on_nonfinite if v == 1 else self.on_spike
            self.n_trips += 1
            self.last_trip_step = gstep
            self.last_trip_kind = kind
            m["trips"].labels(kind, action).inc()
            if _trace.active():
                _trace.start_span("train.guard", attrs={
                    "kind": kind, "action": action, "step": gstep,
                    "loss": repr(float(losses[i])),
                    "grad_norm": repr(float(gnorms[i]))}).end()
            if action == "allow":
                self.n_allowed_spikes += 1
                continue
            if action == "skip":
                self.n_skipped += 1
                m["skipped"].inc()
                if self.n_skipped > self.budget:
                    raise self._abort(
                        gstep, kind, model, losses[i], gnorms[i],
                        reason=f"skip budget exhausted "
                               f"({self.n_skipped} > {self.budget})")
                continue
            if action == "rollback":
                self.n_rollbacks += 1
                m["rollbacks"].inc()
                if self.n_rollbacks > self.max_rollbacks:
                    raise self._abort(
                        gstep, kind, model, losses[i], gnorms[i],
                        reason=f"rollback budget exhausted "
                               f"({self.n_rollbacks} > "
                               f"{self.max_rollbacks})")
                stride = self.rollback_stride * (
                    2 ** (self.n_rollbacks - 1))
                raise GuardRollback(gstep, kind, stride)
            raise self._abort(gstep, kind, model, losses[i],
                              gnorms[i], reason="policy abort")
        if last_norm is not None:
            m["grad_norm"].set(last_norm)

    def escalate(self, step: int, kind: str, reason: str,
                 model=None) -> GuardAbort:
        """Build (and flight-dump) an abort outside ``process`` — the
        path ``Model.fit`` uses when a rollback is requested but no
        checkpoint manager is armed."""
        return self._abort(step, kind, model, np.nan, np.nan,
                           reason=reason)

    def _abort(self, step: int, kind: str, model, loss, gnorm,
               reason: str) -> GuardAbort:
        """The abort verdict: per-tensor non-finite report
        (amp.debugging), step/batch fingerprint, deterministic replay
        command, and a flight-recorder dump carrying all of it."""
        bad = []
        fingerprint: Dict[str, Any] = {"step": int(step), "kind": kind}
        if model is not None:
            try:
                from ..amp.debugging import find_nonfinite
                bad = find_nonfinite({"param": model._params,
                                      "buffer": model._buffers})
            except Exception:  # noqa: BLE001 — attribution best-effort
                bad = []
            fingerprint["batch_shapes"] = getattr(
                model, "_last_batch_shapes", None)
        replay = self._replay_command()
        msg = (f"numeric guard abort ({reason}): {kind} at step "
               f"{step}, loss={float(loss)!r}, "
               f"grad_norm={float(gnorm)!r}; non-finite tensors: "
               f"{bad or ['(loss/grads only)']}; replay: {replay}")
        try:
            from ..observability.flight import dump_flight_record
            dump_flight_record(
                f"guard_abort_step{int(step)}",
                extra={"what": "numeric_guard_abort", "reason": reason,
                       "kind": kind, "fingerprint": fingerprint,
                       "loss": repr(float(loss)),
                       "grad_norm": repr(float(gnorm)),
                       "nonfinite_tensors": bad[:16],
                       "replay": replay,
                       "policy": self.status()})
        except Exception:  # noqa: BLE001 — never mask the abort
            pass
        return GuardAbort(msg, step, kind)

    def _replay_command(self) -> str:
        from . import faults
        if not faults.enabled():
            return ("faults not armed (organic trip) — rerun with "
                    "faults.enable(seed=...) + a data.poison/"
                    "grad.nonfinite schedule to reproduce injected "
                    "trips")
        tail = faults.injected_log()[-4:]
        # no --ci: that mode pins seed=1234 and would ignore --seed
        return (f"python tools/chaos_soak.py --train --seed "
                f"{faults.seed()}  # injected tail: {tail}")

    # -- introspection -------------------------------------------------------
    def status(self) -> Dict[str, Any]:
        """The /statusz bundle (Model's provider embeds it)."""
        return {
            "on_nonfinite": self.on_nonfinite,
            "on_spike": self.on_spike,
            "trips": self.n_trips,
            "skipped": self.n_skipped,
            "skip_budget": self.budget,
            "skip_budget_left": max(self.budget - self.n_skipped, 0),
            "rollbacks": self.n_rollbacks,
            "allowed_spikes": self.n_allowed_spikes,
            "last_trip_step": self.last_trip_step,
            "last_trip_kind": self.last_trip_kind,
        }
