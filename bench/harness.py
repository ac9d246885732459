"""What every driver shares: the refusal to run off the chip, the compile
clock, the device record, the peak table and the traced window."""
from __future__ import annotations

import contextlib
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class NoChip(Exception):
    """The run would not measure the chip's own kernels."""


def preflight(chips: int):
    """Refuse anything but Mosaic kernels on a TPU with enough chips."""
    import jax

    from repro.core.dispatch import forward_execution, kernel_execution
    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"platform is {devices[0].platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} devices, the cell needs {chips}")
    if ops.is_interpret():
        raise NoChip("Pallas kernels would run in interpret mode")
    if kernel_execution("tezo_adam", "pallas") != ("pallas", False):
        raise NoChip("the ZO passes would not run as Mosaic kernels")
    if forward_execution("pallas") != ("pallas", True):
        raise NoChip("the forward would not run the Pallas kernels")


class CompileClock:
    """Seconds XLA spends compiling, from the backend-compile events."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration
            self.count += 1


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def device_record(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@contextlib.contextmanager
def traced_window():
    """Profile the enclosed window into a temporary directory (under
    TMPDIR); yields a dict that holds ``xplane`` (the trace file) and
    ``t0``/``t1`` (the window's host clock, ns) once the block ends.  The
    directory is removed when the caller is done with ``out``."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    out = {"dir": tmp}
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                yield out
        found = list(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))
        out["xplane"] = str(found[0]) if found else None
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def drop_trace(out: dict) -> None:
    shutil.rmtree(out["dir"], ignore_errors=True)


def report_checks(checks: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
