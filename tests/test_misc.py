"""Theory curves, HLO analyzer, serve engine, synth ground truth."""
import numpy as np
import pytest

from repro.core import theory


def test_s_curve_monotone_in_similarity():
    s = np.linspace(0, 1, 21)
    p = theory.detection_probability(s, k=4, m=2, t=100)
    assert (np.diff(p) >= -1e-12).all()
    assert p[0] == pytest.approx(0.0, abs=1e-9)
    assert p[-1] == pytest.approx(1.0, abs=1e-9)


def test_s_curve_shifts_right_with_k_and_m():
    t50_a = theory.s_curve_threshold(4, 2)
    t50_b = theory.s_curve_threshold(8, 2)
    t50_c = theory.s_curve_threshold(4, 8)
    assert t50_b > t50_a and t50_c > t50_a


def test_equivalent_m_drops_when_k_rises():
    """§6.3: more hash functions → lower match threshold, same S-curve."""
    m_new = theory.equivalent_m(k_old=6, m_old=5, k_new=8)
    assert m_new < 5


def test_hlo_analyzer_counts_scan_trips():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_stats import analyze_hlo

    def f(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out.sum()

    comp = jax.jit(f).lower(jnp.ones((32, 32))).compile()
    st = analyze_hlo(comp.as_text())
    dot_flops = 2 * 32**3
    assert st.flops >= 5 * dot_flops, st.flops
    assert st.flops < 20 * dot_flops
    assert st.unknown_trip_whiles == 0


def test_hlo_analyzer_collectives():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.hlo_stats import analyze_hlo
    if jax.device_count() < 2:
        pytest.skip("needs >1 device")


def test_serve_engine_completes():
    from repro.launch.serve import main
    stats = main(["--arch", "smoke", "--requests", "3", "--slots", "2",
                  "--max-new", "4", "--prompt-len", "8", "--max-len", "32"])
    assert stats["requests"] == 3 and stats["generated"] >= 3


def test_synth_ground_truth_arrivals():
    from repro.core import SynthConfig, make_dataset
    ds = make_dataset(SynthConfig(duration_s=120.0, n_stations=2,
                                  n_sources=1, events_per_source=3,
                                  seed=1))
    assert ds.waveforms.shape[0] == 2
    for ev in range(len(ds.event_times)):
        for stn in range(2):
            at = ds.arrival_time(ev, stn)
            assert 0 < at < 120.0
    # reoccurring events share a source template: correlate windows
    if len(ds.event_times) >= 2 and ds.event_sources[0] == \
            ds.event_sources[1]:
        fs = ds.cfg.fs
        n = int(4 * fs)
        a0 = int(ds.arrival_time(0, 0) * fs)
        a1 = int(ds.arrival_time(1, 0) * fs)
        w0 = ds.waveforms[0, a0:a0 + n]
        w1 = ds.waveforms[0, a1:a1 + n]
        c = np.corrcoef(w0, w1)[0, 1]
        assert c > 0.3, c


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The persistent compile cache is $JAX_COMPILATION_CACHE_DIR when set
    (left for JAX to read) and otherwise the checkout's fixed
    ``.jax_cache/``."""
    import pathlib

    import jax
    from repro.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = pathlib.Path(__file__).resolve().parents[1]
        assert enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root
                                                           / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
