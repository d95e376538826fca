"""utils/jitcache counters: hits/misses/compile-seconds bookkeeping.

VERDICT r4 flagged that a 1550 s compile-bound run could not be
diagnosed from its artifact because nothing recorded cache hits vs
misses; these stats are that diagnosis, so they get direct unit
coverage — the listener callbacks, the rounding contract of
``cache_stats()``, and the idempotence of listener registration.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from learningorchestra_tpu.utils import jitcache


@pytest.fixture()
def fresh_stats(monkeypatch):
    stats = {
        "persistent_cache_hits": 0,
        "persistent_cache_misses": 0,
        "backend_compile_s": 0.0,
        "trace_s": 0.0,
        "cache_retrieval_s": 0.0,
    }
    monkeypatch.setattr(jitcache, "_STATS", stats)
    return stats


class TestEventCounters:
    def test_hit_and_miss_events_increment(self, fresh_stats):
        jitcache._on_event("/jax/compilation_cache/cache_hits")
        jitcache._on_event("/jax/compilation_cache/cache_hits")
        jitcache._on_event("/jax/compilation_cache/cache_misses")
        assert fresh_stats["persistent_cache_hits"] == 2
        assert fresh_stats["persistent_cache_misses"] == 1

    def test_unrelated_events_ignored(self, fresh_stats):
        jitcache._on_event("/jax/some/other/event")
        jitcache._on_event("/jax/compilation_cache/cache_hit")  # not plural
        assert fresh_stats["persistent_cache_hits"] == 0
        assert fresh_stats["persistent_cache_misses"] == 0

    def test_extra_kwargs_tolerated(self, fresh_stats):
        # jax.monitoring passes listener kwargs that vary by version
        jitcache._on_event(
            "/jax/compilation_cache/cache_misses", platform="cpu"
        )
        assert fresh_stats["persistent_cache_misses"] == 1


class TestDurationAccumulation:
    def test_compile_and_trace_durations_accumulate(self, fresh_stats):
        jitcache._on_duration(
            "/jax/core/compile/backend_compile_duration", 1.5
        )
        jitcache._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.25
        )
        jitcache._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5)
        assert fresh_stats["backend_compile_s"] == pytest.approx(1.75)
        assert fresh_stats["trace_s"] == pytest.approx(0.5)

    def test_unrelated_durations_ignored(self, fresh_stats):
        jitcache._on_duration("/jax/core/lowering_duration", 9.0)
        assert fresh_stats["backend_compile_s"] == 0.0
        assert fresh_stats["trace_s"] == 0.0
        assert fresh_stats["cache_retrieval_s"] == 0.0

    def test_cache_retrieval_seconds_accumulate(self, fresh_stats):
        event = "/jax/compilation_cache/cache_retrieval_time_sec"
        jitcache._on_duration(event, 0.75)
        jitcache._on_duration(event, 0.5)
        assert fresh_stats["cache_retrieval_s"] == pytest.approx(1.25)
        # the saved-time event beside it is not the load time
        jitcache._on_duration(
            "/jax/compilation_cache/compile_time_saved_sec", 30.0
        )
        assert fresh_stats["cache_retrieval_s"] == pytest.approx(1.25)

    def test_retrieval_gauge_is_fed_like_its_siblings(self, fresh_stats):
        from learningorchestra_tpu.telemetry.metrics import (
            MetricsRegistry,
            _register_jitcache,
        )

        registry = MetricsRegistry()
        _register_jitcache(registry)
        fresh_stats["cache_retrieval_s"] = 9.5
        fresh_stats["backend_compile_s"] = 11.5
        text = registry.render()
        assert "lo_jitcache_cache_retrieval_seconds 9.5" in text
        assert "lo_jitcache_backend_compile_seconds 11.5" in text


class TestCompileSpans:
    """What a compile (or an executable load) leaves in the active
    trace: when, how long, and for which program."""

    def _compile(self, **kwargs):
        from learningorchestra_tpu.telemetry import tracing

        trace = tracing.Trace(name="compile")
        with tracing.activate(trace), tracing.span("phase:fit"):
            jitcache._on_duration(
                "/jax/core/compile/backend_compile_duration", 0.4, **kwargs
            )
        (fit,) = trace.as_dict()["spans"]
        (span,) = fit["children"]
        return span

    def test_backend_span_names_the_program(self, fresh_stats):
        span = self._compile(fun_name="jit(_dt_fit)")
        assert span["name"] == "compile:backend"
        assert span["duration_s"] == pytest.approx(0.4)
        assert span["meta"] == {"compile": True, "program": "jit(_dt_fit)"}

    def test_no_fun_name_no_program(self, fresh_stats):
        # an older jax passes no keyword: the span stays, unnamed
        assert self._compile()["meta"] == {"compile": True}

    def test_aot_span_keeps_its_source_and_gains_the_program(
        self, fresh_stats
    ):
        with jitcache.compile_source("aot", key="lr/8192x16"):
            span = self._compile(fun_name="jit(_fit_segment_impl)")
        assert span["name"] == "compile:aot"
        assert span["meta"] == {
            "compile": True,
            "program": "jit(_fit_segment_impl)",
            "source": "aot",
            "manifest_key": "lr/8192x16",
        }

    def test_a_real_compile_reports_its_function(self, fresh_stats):
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu.telemetry import tracing

        jitcache._register_listeners()

        @jax.jit
        def lo_probe_program(x):
            return x * 3 + 1

        trace = tracing.Trace(name="real")
        with tracing.activate(trace), tracing.span("phase:fit"):
            lo_probe_program(jnp.arange(7)).block_until_ready()
        (fit,) = trace.as_dict()["spans"]
        programs = [
            child["meta"].get("program")
            for child in fit["children"]
            if child["name"] == "compile:backend"
        ]
        assert "jit(lo_probe_program)" in programs


class TestCacheStats:
    def test_floats_rounded_ints_passed_through(self, fresh_stats):
        fresh_stats["backend_compile_s"] = 1.23456
        fresh_stats["trace_s"] = 0.005
        fresh_stats["persistent_cache_hits"] = 7
        stats = jitcache.cache_stats()
        assert stats["backend_compile_s"] == 1.23
        assert stats["trace_s"] == 0.01
        assert stats["persistent_cache_hits"] == 7

    def test_snapshot_is_a_copy(self, fresh_stats):
        snapshot = jitcache.cache_stats()
        snapshot["persistent_cache_hits"] = 999
        assert fresh_stats["persistent_cache_hits"] == 0


class TestListenerRegistration:
    def test_register_listeners_is_idempotent(self, monkeypatch):
        import jax.monitoring

        calls = {"event": 0, "duration": 0}
        monkeypatch.setattr(
            jax.monitoring,
            "register_event_listener",
            lambda fn: calls.__setitem__("event", calls["event"] + 1),
        )
        monkeypatch.setattr(
            jax.monitoring,
            "register_event_duration_secs_listener",
            lambda fn: calls.__setitem__("duration", calls["duration"] + 1),
        )
        monkeypatch.setattr(jitcache, "_LISTENERS_ON", False)
        jitcache._register_listeners()
        jitcache._register_listeners()
        jitcache._register_listeners()
        assert calls == {"event": 1, "duration": 1}


class TestCachePlacement:
    """The directory is placed from outside (JAX's own variable) or is
    one fixed in-checkout path — never cwd- or data-dir-relative."""

    @pytest.fixture()
    def updates(self, monkeypatch):
        import jax

        recorded = []
        monkeypatch.setattr(
            jax.config, "update", lambda key, value: recorded.append((key, value))
        )
        monkeypatch.setattr(jitcache, "_ACTIVE_DIR", None)
        monkeypatch.setattr(jitcache, "_LISTENERS_ON", True)
        return recorded

    def test_env_placed_cache_configures_no_directory(
        self, updates, monkeypatch, tmp_path
    ):
        import jax

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert (
            jitcache.enable_compile_cache()
            == jax.config.jax_compilation_cache_dir
        )
        assert "jax_compilation_cache_dir" not in [key for key, _ in updates]
        # the key-portability switch still applies
        assert ("jax_persistent_cache_enable_xla_caches", "") in updates

    def test_default_is_fixed_in_checkout_path_from_any_cwd(
        self, updates, monkeypatch, tmp_path
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("LO_DATA_DIR", str(tmp_path / "data"))
        monkeypatch.chdir(tmp_path)
        expected = os.path.join(REPO, ".jit_cache")
        assert jitcache.enable_compile_cache() == expected
        assert ("jax_compilation_cache_dir", expected) in updates

    def test_jax_itself_honours_the_variable(self, tmp_path):
        proc = subprocess.run(
            [
                sys.executable,
                "-c",
                "from learningorchestra_tpu.utils.jitcache import "
                "enable_compile_cache as e; import jax; "
                "print(e()); print(jax.config.jax_compilation_cache_dir)",
            ],
            env=dict(
                os.environ,
                PYTHONPATH=REPO,
                JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "placed"),
            ),
            cwd=tmp_path,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-800:]
        assert proc.stdout.split() == [str(tmp_path / "placed")] * 2
