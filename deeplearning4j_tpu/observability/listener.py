"""TelemetryListener: the registry's tap into the listener pipeline.

The fit loops already time their own phases (staging/dispatch/listeners) at
the call sites; what a listener adds is the *model-visible* view — wall time
between iterations, the training score, device memory — sampled through the
same ``iteration_done`` hook every other listener uses, so attaching
telemetry needs no fit-loop changes on the user's side.

Device-time discipline: the ONE sync point is ``float(loss)`` through
``LazyScore.score_value`` (every other host read in a fit loop would drain
the dispatch pipeline — the reason LazyScore exists; whether
``block_until_ready`` agrees with it on a local chip is to be re-checked,
ROADMAP S0). So device time is sampled by timing that exact read, every
``sync_every`` iterations, and the fit loop's cached read afterwards is
free. ``memory_stats()`` returns None on CPU and on some backends; the HBM
gauge degrades to 0.0 rather than vanishing so dashboards keep the series.
"""
from __future__ import annotations

import time
from typing import Optional

from .metrics import global_registry
from .names import (DEVICE_HBM_BYTES, DEVICE_HBM_PEAK_BYTES,
                    STEP_DEVICE_SYNC_SECONDS, STEP_HOST_SECONDS,
                    TRAIN_ITERATION, TRAIN_SCORE)


def record_hbm_gauges(registry=None) -> None:
    """Set ``dl4j_device_hbm_bytes{device=...}`` for every local device,
    None-safe (CPU backends report no memory_stats -> 0.0)."""
    reg = registry if registry is not None else global_registry()
    gauge = reg.gauge(DEVICE_HBM_BYTES,
                      "bytes in use per device (0 when the backend "
                      "reports no memory_stats, e.g. CPU)")
    peak = reg.gauge(DEVICE_HBM_PEAK_BYTES,
                     "peak bytes in use per device (0 when unreported)")
    try:
        import jax
        devices = jax.local_devices()
    except Exception:  # pragma: no cover - no backend at all
        return
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        stats = stats or {}
        label = f"{d.platform}:{d.id}"
        gauge.labels(device=label).set(stats.get("bytes_in_use", 0) or 0)
        peak.labels(device=label).set(stats.get("peak_bytes_in_use", 0) or 0)


class TelemetryListener:
    """IterationListener feeding the metrics registry (and optionally the
    StatsStorage pipeline) from any fit loop.

    Parameters
    ----------
    sync_every: sample device time by timing ``float(model.score_value)``
        every N iterations (0 disables; the read is the trusted lazy sync,
        so sampled iterations cost exactly one host round-trip that the
        score-reading listeners would have paid anyway).
    hbm_every: refresh per-device HBM gauges every N iterations.
    router: optional ``StatsStorageRouter``; when given, a ``StatsReport``
        carrying score/iteration-time/device-memory is posted every
        ``report_every`` iterations so the training UI's existing charts see
        telemetry without a separate StatsListener.
    snapshot_path: optional JSONL path; a full registry snapshot is appended
        on every epoch end (the ``--telemetry-out`` format).
    """

    def __init__(self, sync_every: int = 10, hbm_every: int = 10,
                 router=None, report_every: int = 1,
                 snapshot_path: Optional[str] = None,
                 worker_id: str = "main", registry=None):
        self.sync_every = max(0, sync_every)
        self.hbm_every = max(1, hbm_every)
        self.router = router
        self.report_every = max(1, report_every)
        self.snapshot_path = snapshot_path
        self.worker_id = worker_id
        self._registry = registry
        self._last_done: Optional[float] = None
        self._session_id = f"telemetry_{int(time.time() * 1000)}"
        reg = self.registry
        self._step_hist = reg.histogram(
            STEP_HOST_SECONDS,
            "host wall time between consecutive iterations").labels(
                worker=worker_id)
        self._sync_hist = reg.histogram(
            STEP_DEVICE_SYNC_SECONDS,
            "time to materialize float(loss) at the trusted sync point"
        ).labels(worker=worker_id)
        self._score_gauge = reg.gauge(
            TRAIN_SCORE, "last synced training score").labels(
                worker=worker_id)
        self._iter_gauge = reg.gauge(
            TRAIN_ITERATION, "last completed iteration").labels(
                worker=worker_id)

    @property
    def registry(self):
        return self._registry if self._registry is not None \
            else global_registry()

    # ------------------------------------------------------------ listener
    def iteration_done(self, model, iteration: int) -> None:
        now = time.perf_counter()
        if self._last_done is not None:
            self._step_hist.observe(now - self._last_done)
        self._last_done = now
        self._iter_gauge.set(iteration)

        score = None
        if self.sync_every and iteration % self.sync_every == 0:
            t0 = time.perf_counter()
            score = float(model.score_value)
            self._sync_hist.observe(time.perf_counter() - t0)
            self._score_gauge.set(score)

        if iteration % self.hbm_every == 0:
            record_hbm_gauges(self.registry)

        if self.router is not None and iteration % self.report_every == 0:
            self._post_report(model, iteration, score)

    def on_epoch_start(self, model) -> None:
        pass

    def on_epoch_end(self, model) -> None:
        # epoch boundary: refresh gauges and (optionally) persist a snapshot
        record_hbm_gauges(self.registry)
        if self.snapshot_path:
            self.registry.write_jsonl(self.snapshot_path,
                                      source="TelemetryListener",
                                      epoch=getattr(model, "epoch", None))

    # ------------------------------------------------------------- bridge
    def _post_report(self, model, iteration: int, score) -> None:
        from deeplearning4j_tpu.ui.stats import StatsReport

        r = StatsReport(self._session_id, self.worker_id,
                        int(time.time() * 1000))
        r.iteration = iteration
        if score is not None:
            r.score = score
        snap = self.registry.snapshot()
        hbm = snap.get(DEVICE_HBM_BYTES, {}).get("series", [])
        if hbm:
            r.device_mem_bytes = int(max(s["value"] for s in hbm))
        self.router.put_update(r)
