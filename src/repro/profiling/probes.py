"""Controllable memory-traffic antagonist (§4.2's co-run counterpart).

PCCS calibration needs (own, external) → slowdown samples, which means
co-running the target layer group against an antagonist that requests a
*known, controllable* share of the contention domain's bandwidth.  This
module is that antagonist:

* :func:`stream_once` — one streaming pass over a buffer (reads 2
  operands, writes 1: a saxpy), dispatched across the repo-wide backend
  idiom (:mod:`repro.kernels.ops`): a Pallas kernel on TPU
  (``pallas``/``pallas_interpret``) or the identical jnp expression under
  jit elsewhere (``xla``); ``auto`` picks by ``jax.default_backend()``
  (:func:`repro.kernels.ops.resolve`).
* :func:`measure_peak_bandwidth` — calibrate the probe itself: achieved
  bytes/s of back-to-back full-duty streaming, which anchors duty-cycled
  demand levels to fractions of *measured* capacity.
* :class:`MemoryProbe` — a background thread issuing streaming passes at
  a duty cycle: ``demand=0.6`` streams 60% of each period and idles 40%,
  so its requested throughput is ~0.6× the full-duty rate.  Used to sweep
  external demand against real kernel targets on hardware; the virtual
  SoC takes the demand level directly (its ``external=`` knob) so CI
  never depends on wall-clock co-scheduling.
"""
from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import LANES, lane_tiling, resolve

from .harness import TimerConfig, measure_wallclock

#: streaming traffic per pass: x (read) + y (read) + out (write).
_BYTES_PER_ELEM = 3 * 4          # float32


def _stream_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] * jnp.float32(1.0000001) + y_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _pallas_stream(x, y, *, block: int, interpret: bool):
    n = x.shape[0]
    rb, nb = lane_tiling(n, block)
    pad = nb * rb * LANES - n
    tile = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _stream_kernel,
        grid=(nb,),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nb * rb, LANES), x.dtype),
        interpret=interpret,
    )(jnp.pad(x, (0, pad)).reshape(-1, LANES),
      jnp.pad(y, (0, pad)).reshape(-1, LANES))
    return out.reshape(-1)[:n]


@jax.jit
def _xla_stream(x, y):
    return x * jnp.float32(1.0000001) + y


def stream_once(x, y, *, backend: str = "auto", block: int = 65536):
    """One antagonist pass: reads ``x``/``y`` fully, writes their saxpy."""
    b = resolve("stream", backend)
    if b == "xla":
        return _xla_stream(x, y)
    if b in ("pallas", "pallas_interpret"):
        return _pallas_stream(x, y, block=block,
                              interpret=(b == "pallas_interpret"))
    raise ValueError(f"unknown backend {b!r}")


def make_buffers(mbytes: float = 32.0):
    """Streaming operand pair sized so one pass moves ~``mbytes`` MB."""
    n = max(1024, int(mbytes * 1e6 / _BYTES_PER_ELEM))
    x = jnp.arange(n, dtype=jnp.float32) * jnp.float32(1e-6)
    return x, x + jnp.float32(1.0)


def stream_bytes(x) -> float:
    """Traffic one :func:`stream_once` pass over ``x`` moves (bytes)."""
    return float(x.size * _BYTES_PER_ELEM)


def measure_peak_bandwidth(*, mbytes: float = 32.0, backend: str = "auto",
                           timer: TimerConfig = TimerConfig(warmup=2,
                                                            repeats=5),
                           ) -> float:
    """Achieved bytes/s of full-duty streaming — the probe's own peak.

    Demand fractions handed to :class:`MemoryProbe` (and recorded in
    calibration samples) are relative to this measured rate, the same way
    the paper's "requested memory throughput (%)" is relative to measured
    EMC saturation, not the datasheet number.
    """
    x, y = make_buffers(mbytes)
    m = measure_wallclock(lambda: stream_once(x, y, backend=backend),
                          timer=timer, name=f"stream-{mbytes}MB")
    return stream_bytes(x) / (m.median_ms * 1e-3)


class MemoryProbe:
    """Duty-cycled background antagonist thread.

    ``demand`` in (0, 1] is the fraction of each ``period_ms`` window spent
    streaming; the rest idles, so requested throughput scales linearly
    with ``demand`` while the *burst* rate stays at the device's streaming
    peak — the same shape PCCS's microbenchmark antagonists have.
    """

    def __init__(self, demand: float = 1.0, *, mbytes: float = 8.0,
                 backend: str = "auto", period_ms: float = 5.0):
        if not 0.0 < demand <= 1.0:
            raise ValueError("demand must be in (0, 1]")
        self.demand = float(demand)
        self.backend = backend
        self.period_s = period_ms * 1e-3
        self._x, self._y = make_buffers(mbytes)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        #: streaming passes issued (for achieved-rate accounting).
        self.passes = 0

    def _loop(self):
        burst_s = self.period_s * self.demand
        while not self._stop.is_set():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < burst_s:
                jax.block_until_ready(
                    stream_once(self._x, self._y, backend=self.backend))
                self.passes += 1
                if self._stop.is_set():
                    return
            idle = self.period_s - (time.perf_counter() - t0)
            if idle > 0:
                self._stop.wait(idle)

    def __enter__(self) -> "MemoryProbe":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def start(self) -> None:
        if self._thread is not None:
            raise RuntimeError("probe already started")
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10.0)
        self._thread = None

    def bytes_per_pass(self) -> float:
        return stream_bytes(self._x)
