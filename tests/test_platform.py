"""Process-level runtime facts (``utils/platform.py``): where the persistent
compilation cache is placed, what the cache did, and which devices the
entry points report."""

import json
import os
import subprocess
import sys

import jax
import pytest

from simclr_tpu.utils import platform

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them, so a
    placement test never turns the cache on for the rest of the session."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


class TestCompilationCachePlacement:
    def test_env_set_leaves_the_directory_alone(self, monkeypatch, config_updates):
        monkeypatch.setenv(platform.CACHE_DIR_ENV, "/somewhere/outside")
        assert platform.configure_compilation_cache() == "/somewhere/outside"
        assert config_updates == [METADATA_IN_KEY]

    def test_unset_places_it_in_the_checkout(self, monkeypatch, config_updates):
        monkeypatch.delenv(platform.CACHE_DIR_ENV, raising=False)
        expected = os.path.join(REPO_ROOT, ".jax_cache")
        assert platform.configure_compilation_cache() == expected
        assert config_updates == [
            METADATA_IN_KEY, ("jax_compilation_cache_dir", expected)
        ]

    def test_the_key_covers_metadata_so_scopes_are_the_programs_own(self):
        """Two programs that differ in their ``jax.named_scope`` names alone
        lower to the same text once metadata is stripped, which is all JAX's
        default cache key sees: the second would be handed the first's
        executable, op_names and all, and ``step_scopes.json`` (read from the
        executable's text) would name another program's scopes."""
        import jax.numpy as jnp

        def step(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.tanh(x) * 2.0
            return jax.jit(f).lower(jnp.ones((8,)))

        a, b = step("encoder"), step("ContrastiveModel")
        assert a.as_text() == b.as_text()
        assert a.as_text(debug_info=True) != b.as_text(debug_info=True)

    def test_same_directory_from_two_processes(self, tmp_path):
        """A directory that moves never hits: two processes started from
        different working directories must agree, and JAX itself must end up
        with that value."""
        code = (
            "import jax\n"
            "from simclr_tpu.utils.platform import configure_compilation_cache\n"
            "print(configure_compilation_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
        )
        env = {k: v for k, v in os.environ.items() if k != platform.CACHE_DIR_ENV}
        env["PYTHONPATH"] = REPO_ROOT
        seen = [
            subprocess.run(
                [sys.executable, "-c", code], cwd=cwd, env=env, text=True,
                capture_output=True, check=True, timeout=120,
            ).stdout.split()
            for cwd in (REPO_ROOT, str(tmp_path))
        ]
        expected = os.path.join(REPO_ROOT, ".jax_cache")
        assert seen == [[expected, expected], [expected, expected]]

    def test_nothing_else_in_the_repo_sets_a_cache_directory(self):
        hits = subprocess.run(
            ["grep", "-rlE", "compilation_cache_dir|set_cache_dir",
             "--include=*.py", "simclr_tpu", "scripts", "chip_smoke.py",
             "__graft_entry__.py"],
            cwd=REPO_ROOT, text=True, capture_output=True,
        ).stdout.split()
        assert hits == ["simclr_tpu/utils/platform.py"]


def test_cache_stats_count_jax_monitoring_events(monkeypatch):
    monkeypatch.setattr(platform, "_events", type(platform._events)())
    platform._count_event("/jax/compilation_cache/cache_hits")
    platform._count_event("/jax/compilation_cache/cache_hits")
    platform._count_event("/jax/compilation_cache/cache_misses")
    platform._count_event("/jax/compilation_cache/compile_requests_use_cache")
    stats = platform.compilation_cache_stats()
    assert (stats["hits"], stats["misses"]) == (2, 1)
    assert "dir" in stats


def test_backend_compilations_counts_programs_built():
    """One reading per program handed to the compiler: a new jitted function
    is one, its second call none."""
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7)
    before = platform.backend_compilations()
    f(x)
    assert platform.backend_compilations() == before + 1
    f(x)
    assert platform.backend_compilations() == before + 1


def test_device_identity_is_what_jax_reports():
    assert platform.device_identity() == {
        "platform": "cpu",
        "kind": jax.devices()[0].device_kind,
        "count": 8,
    }


def test_pretrain_results_and_log_name_the_device(tmp_path, monkeypatch):
    """Every number the trainer writes says which devices it came from."""
    from simclr_tpu import main as pretrain
    from simclr_tpu.config import load_config
    from tests.helpers import TinyContrastive

    monkeypatch.setattr(pretrain, "build_model", lambda cfg: TinyContrastive())
    lines = []
    monkeypatch.setattr(
        pretrain.logger, "info", lambda msg, *args: lines.append(msg % args)
    )
    cfg = load_config(
        "config",
        overrides=[
            "parameter.epochs=1", "parameter.warmup_epochs=0",
            "experiment.batches=1", "experiment.synthetic_data=true",
            "experiment.synthetic_size=8", f"experiment.save_dir={tmp_path}",
        ],
    )
    summary = pretrain.run_pretrain(cfg)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 8}
    assert summary["device"] == device
    results = json.loads((tmp_path / "pretrain_results.json").read_text())
    assert results["device"] == device
    assert set(results["compile_cache"]) == {"dir", "hits", "misses"}
    first = next(line for line in lines if line.startswith("pretrain "))
    assert "platform=cpu" in first and "devices=8" in first
    assert f"device_kind={device['kind']!r}" in first
