"""Seeded network waveform traffic: noise plus repeating sources.

One general generator reads every traffic mix from its parameter file
(``bench/traffic/<name>.json``); the station count, the components a
station records (``channels``) and the sample rate come from the cell's
configuration. Each stream is Gaussian background noise plus repeating
events from ``sources`` templates, one event every ``event_interval_s``
seconds across the network, each arriving on every station after a
per-(source, station) delay. A component is one more stream of the
station: an event appears on all of a station's components at that one
onset, each component with its own template per source (fixed across the
repeats) and its own noise; it is a distinct projection of the source,
not a model of polarisation or particle motion. Arrivals are aligned to the
fingerprint lag grid so the repeats of one source give near-identical
fingerprints and hash-collide (sub-lag offsets shift the whole spectral
image, and such repeats almost never collide at the paper's widths).

Every seed gets the same number of events at the same rate; the seed
draws the templates, the order of the sources, the jitter of each event
inside its interval, the delays, the amplitudes and the noise. Chunk
``k`` of the stream is a pure function of (seed, k), so a chunk can be
made again after the run for the reference. Components after the first
draw their templates and noise from random streams of their own, so
component 0 of every station is the one-component stream, byte for
byte.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"

# stream time the event schedule covers; far beyond any run's reach
HORIZON_S = 60 * 86400.0


@dataclasses.dataclass(frozen=True)
class TrafficMix:
    name: str
    event_interval_s: float
    sources: int
    event_snr: float = 8.0
    event_duration_s: float = 6.0
    event_freq_hz: tuple = (5.0, 14.0)
    delay_lags: tuple = (1, 5)
    noise_sigma: float = 1.0
    stats_fingerprints: int = 128
    why: str = ""


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> TrafficMix:
    raw = json.loads((directory / f"{name}.json").read_text())
    raw = {k: tuple(v) if isinstance(v, list) else v for k, v in raw.items()}
    return TrafficMix(name=name, **raw)


def _template(rng: np.random.Generator, n: int, fs: float,
              freq_hz: tuple) -> np.ndarray:
    """P + S wave burst: two damped oscillations, S delayed and larger
    (the event shape of the program's synthetic data generator)."""
    t = np.arange(n) / fs
    fp = rng.uniform(*freq_hz)
    fs_ = rng.uniform(*freq_hz)
    s_delay = rng.uniform(0.8, 2.0)
    tau_p, tau_s = rng.uniform(0.3, 0.8), rng.uniform(0.8, 1.8)
    p = np.exp(-t / tau_p) * np.sin(2 * np.pi * fp * t + rng.uniform(0, 6.28))
    ts = np.clip(t - s_delay, 0, None)
    s = (np.exp(-ts / tau_s) * np.sin(2 * np.pi * fs_ * ts)
         * (t >= s_delay) * rng.uniform(1.5, 2.5))
    return (p + s).astype(np.float32)


class NetworkStream:
    """The stream of one run: ``chunk(k)`` is samples
    ``[k * chunk_samples, (k + 1) * chunk_samples)`` of every component
    of every station, station-major and component-minor."""

    def __init__(self, mix: TrafficMix, stations: int, seed: int, fs: float,
                 lag_samples: int, chunk_samples: int, channels: int = 1):
        self.mix = mix
        self.stations = stations
        self.channels = channels
        self.seed = int(seed) % 2**64
        self.fs = fs
        self.lag = lag_samples
        self.chunk_samples = chunk_samples
        rng = np.random.default_rng([self.seed, 0x7EA])
        n_tpl = int(round(mix.event_duration_s * fs))
        tpl_rngs = [rng] + [np.random.default_rng([self.seed, 0x7EA, c])
                            for c in range(1, channels)]
        # (sources, channels, samples): one template per (source, component)
        self.templates = np.stack(
            [np.stack([_template(r, n_tpl, fs, mix.event_freq_hz)
                       for _ in range(mix.sources)]) for r in tpl_rngs],
            axis=1)
        self.delays = self.lag * rng.integers(
            mix.delay_lags[0], mix.delay_lags[1],
            (mix.sources, stations))
        interval = int(round(mix.event_interval_s * fs)) // self.lag
        n_ev = int(HORIZON_S * fs) // (interval * self.lag)
        # one event per interval, jittered on the lag grid inside its first
        # half; every cycle of ``sources`` events uses each source once
        jitter = rng.integers(0, max(1, interval // 2), n_ev)
        self.times = ((np.arange(n_ev) * interval + jitter + 1)
                      * self.lag).astype(np.int64)
        cycles = -(-n_ev // mix.sources)
        self.event_source = np.concatenate(
            [rng.permutation(mix.sources) for _ in range(cycles)])[:n_ev]
        self.amps = (mix.event_snr * mix.noise_sigma * rng.uniform(
            0.9, 1.1, (n_ev, stations))).astype(np.float32)

    def chunk(self, k: int) -> np.ndarray:
        """(stations * channels, chunk_samples) float32."""
        n = self.chunk_samples
        out = np.empty((self.stations, self.channels, n), np.float32)
        for c in range(self.channels):
            key = [self.seed, 1, k] + ([c] if c else [])
            out[:, c] = np.random.default_rng(key).standard_normal(
                (self.stations, n), dtype=np.float32)
        out *= np.float32(self.mix.noise_sigma)
        s0, s1 = k * n, (k + 1) * n
        n_tpl = self.templates.shape[-1]
        reach = int(self.delays.max()) + n_tpl
        lo = np.searchsorted(self.times, s0 - reach, "left")
        hi = np.searchsorted(self.times, s1, "left")
        for e in range(lo, hi):
            src = self.event_source[e]
            tpl = self.templates[src]
            for st in range(self.stations):
                a = self.times[e] + self.delays[src, st]
                b0, b1 = max(a, s0), min(a + n_tpl, s1)
                if b0 < b1:
                    out[st, :, b0 - s0:b1 - s0] += (self.amps[e, st]
                                                    * tpl[:, b0 - a:b1 - a])
        return out.reshape(self.stations * self.channels, n)

    def span(self, n_samples: int) -> np.ndarray:
        """The first ``n_samples`` of every stream, chunk by chunk."""
        n_chunks = -(-n_samples // self.chunk_samples)
        parts = [self.chunk(k) for k in range(n_chunks)]
        return np.concatenate(parts, axis=1)[:, :n_samples]
