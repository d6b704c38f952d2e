"""Experiment monitors.

Re-creation of ``deepspeed/monitor/monitor.py:30`` (``MonitorMaster`` fanning
out to TensorBoard / W&B / CSV writers).  Events are ``(name, value, step)``
tuples written at gradient-accumulation boundaries by the engine.
"""
from __future__ import annotations

import csv
import os
from typing import Any, List, Tuple

from deepspeed_tpu.utils.logging import logger

Event = Tuple[str, float, int]


class Monitor:
    def __init__(self, config):
        self.enabled = bool(getattr(config, "enabled", False))

    def write_events(self, events: List[Event]) -> None:  # pragma: no cover
        raise NotImplementedError


class CSVMonitor(Monitor):
    """``csv_monitor`` config subtree (reference ``csv_monitor.py:12``).

    Per-series file handles stay open across ``write_events`` calls
    (one ``open()`` per series for the process's lifetime, not one per
    event — a serving-health flush emits dozens of series per step).
    Rows are flushed per call so concurrent readers see them; ``close``
    releases the handles.
    """

    def __init__(self, config):
        super().__init__(config)
        self.output_path = getattr(config, "output_path", "") or "./csv_monitor"
        self.job_name = getattr(config, "job_name", "DeepSpeedTPUJobName")
        self._files = {}                   # series name -> (handle, writer)
        if self.enabled:
            os.makedirs(os.path.join(self.output_path, self.job_name),
                        exist_ok=True)

    def _writer(self, name: str):
        ent = self._files.get(name)
        if ent is None:
            fname = os.path.join(self.output_path, self.job_name,
                                 name.replace("/", "_") + ".csv")
            new = not os.path.exists(fname) or os.path.getsize(fname) == 0
            f = open(fname, "a", newline="")
            w = csv.writer(f)
            if new:
                w.writerow(["step", name])
            ent = self._files[name] = (f, w)
        return ent

    def write_events(self, events: List[Event]) -> None:
        if not self.enabled:
            return
        touched = set()
        for name, value, step in events:
            f, w = self._writer(name)
            w.writerow([step, value])
            touched.add(name)
        for name in touched:
            self._files[name][0].flush()

    def close(self) -> None:
        for f, _ in self._files.values():
            try:
                f.close()
            except Exception:
                pass
        self._files = {}

    def __del__(self):  # best-effort: rows are already flushed per call
        self.close()


class TensorBoardMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        self.writer = None
        if self.enabled:
            try:
                from torch.utils.tensorboard import SummaryWriter

                path = os.path.join(
                    getattr(config, "output_path", "") or "./runs",
                    getattr(config, "job_name", "DeepSpeedTPUJobName"))
                self.writer = SummaryWriter(log_dir=path)
            except Exception as e:
                logger.warning(f"TensorBoard unavailable ({e}); disabled")
                self.enabled = False

    def write_events(self, events: List[Event]) -> None:
        if not self.enabled or self.writer is None:
            return
        for name, value, step in events:
            self.writer.add_scalar(name, value, step)
        self.writer.flush()


class WandbMonitor(Monitor):
    def __init__(self, config):
        super().__init__(config)
        if self.enabled:
            try:
                import wandb

                wandb.init(project=getattr(config, "project", "deepspeed_tpu"),
                           group=getattr(config, "group", None))
                self._wandb = wandb
            except Exception as e:
                logger.warning(f"wandb unavailable ({e}); disabled")
                self.enabled = False

    def write_events(self, events: List[Event]) -> None:
        if not self.enabled:
            return
        for name, value, step in events:
            self._wandb.log({name: value}, step=step)


class CometMonitor(Monitor):
    """``comet`` config subtree (reference ``monitor/comet.py:23``
    CometMonitor): stream to a comet_ml experiment, throttling each
    metric name to every ``samples_log_interval`` samples."""

    def __init__(self, config):
        super().__init__(config)
        self.samples_log_interval = int(
            getattr(config, "samples_log_interval", 100) or 100)
        self._last_logged = {}
        self.experiment = None
        if self.enabled:
            try:
                import comet_ml

                self.experiment = comet_ml.start(
                    api_key=getattr(config, "api_key", None),
                    project=getattr(config, "project", None),
                    workspace=getattr(config, "workspace", None),
                    experiment_key=getattr(config, "experiment_key", None),
                    mode=getattr(config, "mode", None),
                    online=getattr(config, "online", None))
                name = getattr(config, "experiment_name", None)
                if name:
                    self.experiment.set_name(name)
            except Exception as e:
                logger.warning(f"comet_ml unavailable ({e}); disabled")
                self.enabled = False

    def _needs_logging(self, name: str, step: int) -> bool:
        last = self._last_logged.get(name)
        if last is not None and step - last < self.samples_log_interval:
            return False
        self._last_logged[name] = step
        return True

    def write_events(self, events: List[Event]) -> None:
        if not self.enabled or self.experiment is None:
            return
        for name, value, step in events:
            if self._needs_logging(name, step):
                self.experiment.log_metric(name=name, value=value,
                                           step=step)


class MonitorMaster(Monitor):
    """Fan-out to every enabled writer; only process 0 writes."""

    def __init__(self, monitor_config):
        self.tb = TensorBoardMonitor(monitor_config.tensorboard)
        self.csv = CSVMonitor(monitor_config.csv_monitor)
        self.wandb = WandbMonitor(monitor_config.wandb)
        self.comet = CometMonitor(getattr(monitor_config, "comet", None))
        self.enabled = (self.tb.enabled or self.csv.enabled or
                        self.wandb.enabled or self.comet.enabled)

    def close(self) -> None:
        """Release writer resources (the CSV monitor's open per-series
        handles; TB flushes per write already)."""
        self.csv.close()

    def write_events(self, events: List[Event]) -> None:
        import jax

        if jax.process_index() != 0:
            return
        for m in (self.tb, self.csv, self.wandb, self.comet):
            if m.enabled:
                m.write_events(events)

    def write_sdc_health(self, sdc_counters: dict, step: int) -> None:
        """Surface the swap path's silent-data-corruption counters
        (``NvmeOptimizerSwapper.sdc_counters`` — cumulative detection /
        re-read-recovery / quarantine totals).  A host with flaky
        DRAM/NVMe shows up as a climbing ``Sdc/mismatches`` series long
        before it would have surfaced as unexplained loss drift."""
        self.write_events([(f"Sdc/{name}", float(value), step)
                           for name, value in sorted(sdc_counters.items())])

    def write_serving_health(self, serving_stages: dict,
                             step: int) -> None:
        """Surface a serving engine's host-path breakdown
        (``engine.serving_stages()`` — per-dispatch
        plan/upload/dispatch/device/harvest ms plus
        ``host_bound_fraction``) as ``Serving/*`` series.  A serving
        fleet whose ``Serving/host_bound_fraction`` climbs toward 1.0
        is wasting its accelerators on host scheduling — the signal the
        pipelined host path exists to drive down.  One-level sub-dicts
        (the ``kv_tiering`` spill/restore counters, the ``prefix_cache``
        hit counters) flatten to ``Serving/<group>/<name>`` series — a
        climbing ``Serving/kv_tiering/quarantined`` flags a host whose
        spill media is corrupting parked KV pages."""
        events = []
        for name, value in sorted(serving_stages.items()):
            if isinstance(value, dict):
                events += [(f"Serving/{name}/{k}", float(v), step)
                           for k, v in sorted(value.items())
                           if isinstance(v, (int, float))]
            elif isinstance(value, (int, float)):
                events.append((f"Serving/{name}", float(value), step))
        self.write_events(events)

    def write_metrics(self, registry: Any = None, step: int = 0) -> None:
        """Surface the telemetry :class:`MetricsRegistry` as
        ``Metrics/*`` series: counters and gauges by value, histograms
        as ``_count``/``_sum``/``_p50``/``_p99`` scalars (the registry's
        ``scalar_summary()`` view).  ``registry`` defaults to the
        process singleton; a dict is accepted for pre-flattened views.
        The SLO burn per objective rides along when an ``SLOSet`` is
        attached to the registry — ``Metrics/slo/<objective>_burn_rate``
        crossing 1.0 is the page-the-operator signal."""
        if registry is None:
            from deepspeed_tpu.telemetry.metrics import metrics as registry
        summary = (dict(registry) if isinstance(registry, dict)
                   else registry.scalar_summary())
        events = [(f"Metrics/{name}", float(value), step)
                  for name, value in sorted(summary.items())
                  if isinstance(value, (int, float))]
        slo = getattr(registry, "slo", None)
        if slo is not None:
            events += [(f"Metrics/slo/{k}", float(v), step)
                       for k, v in sorted(slo.flat_summary().items())
                       if isinstance(v, (int, float))]
        self.write_events(events)

    def write_comm_health(self, straggler_report: dict, step: int) -> None:
        """Surface the cross-rank straggler report
        (``comm.straggler_report()``) as metric events: per-op latency
        spread plus the named straggler rank (-1 when no rank cleared
        the naming thresholds).  A real slow rank shows up as a
        persistent nonnegative ``straggler_rank`` series."""
        events: List[Event] = []
        for op, rec in sorted(straggler_report.items()):
            rank = rec.get("straggler_rank")
            events.append((f"Comm/{op}/straggler_rank",
                           float(-1 if rank is None else rank), step))
            events.append((f"Comm/{op}/straggler_spread_ms",
                           float(rec.get("spread_ms", 0.0)), step))
        if events:
            self.write_events(events)
