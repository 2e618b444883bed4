"""Lightweight Hydra-style configuration system.

The reference drives every entry point through Hydra YAML groups plus dotted
CLI overrides (e.g. ``parameter.epochs=200``) — see
``/root/reference/main.py:134`` and ``/root/reference/conf/config.yaml``.
This module reproduces that ergonomic surface (YAML files, a ``defaults`` list
for group composition, dotted overrides with YAML-typed values, startup
validation) without the Hydra dependency, and keeps the reference's key tree
(``parameter.*``, ``experiment.*``) so recipes translate 1:1.

Differences from the reference, by design:
  * no working-directory switching — runs write to ``experiment.save_dir``
    (default ``results/<name>/seed-<seed>/<timestamp>``) without chdir;
  * the ``distributed`` group becomes ``mesh`` (a TPU device-mesh spec)
    because SPMD-with-jit replaces process-per-GPU DDP
    (``/root/reference/distributed_utils.py:8-24`` has no TPU analogue).
"""

from __future__ import annotations

import copy
import datetime
import os
from typing import Any, Iterable

import yaml

_CONF_DIR = os.path.join(os.path.dirname(__file__), "conf")


class ConfigError(ValueError):
    """Raised on malformed config files, overrides, or failed validation."""


class Config:
    """A nested, attribute-accessible configuration node.

    Behaves like a read-mostly dict-of-dicts with attribute access
    (``cfg.parameter.epochs``), mirroring OmegaConf's DictConfig surface that
    the reference code relies on.
    """

    def __init__(self, data: dict[str, Any] | None = None):
        object.__setattr__(self, "_data", {})
        for key, value in (data or {}).items():
            self._data[key] = Config(value) if isinstance(value, dict) else value

    # -- mapping protocol -------------------------------------------------
    def __getitem__(self, key: str) -> Any:
        return self._data[key]

    def __setitem__(self, key: str, value: Any) -> None:
        self._data[key] = Config(value) if isinstance(value, dict) else value

    def __contains__(self, key: str) -> bool:
        return key in self._data

    def __iter__(self) -> Iterable[str]:
        return iter(self._data)

    def keys(self):
        return self._data.keys()

    def items(self):
        return self._data.items()

    def get(self, key: str, default: Any = None) -> Any:
        return self._data.get(key, default)

    # -- attribute protocol -----------------------------------------------
    def __getattr__(self, key: str) -> Any:
        try:
            return self._data[key]
        except KeyError:
            raise AttributeError(f"config has no key {key!r}; have {list(self._data)}")

    def __setattr__(self, key: str, value: Any) -> None:
        self[key] = value

    def __repr__(self) -> str:
        return f"Config({self.to_dict()!r})"

    def __eq__(self, other: object) -> bool:
        if isinstance(other, Config):
            return self.to_dict() == other.to_dict()
        if isinstance(other, dict):
            return self.to_dict() == other
        return NotImplemented

    # -- conversion --------------------------------------------------------
    def to_dict(self) -> dict[str, Any]:
        return {
            k: v.to_dict() if isinstance(v, Config) else copy.deepcopy(v)
            for k, v in self._data.items()
        }

    def to_yaml(self) -> str:
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    # -- dotted access -----------------------------------------------------
    def select(self, dotted: str, default: Any = None) -> Any:
        node: Any = self
        for part in dotted.split("."):
            if not isinstance(node, Config) or part not in node:
                return default
            node = node[part]
        return node

    def update_dotted(self, dotted: str, value: Any, allow_new: bool = True) -> None:
        """Set a dotted key. With ``allow_new=False`` (strict mode, used for
        CLI overrides) a path that does not already exist raises — catching
        typos like ``parameter.eopchs=5`` that would otherwise silently no-op
        (Hydra strict-mode semantics; opt into new keys with a ``+`` prefix).
        """
        parts = dotted.split(".")
        node = self
        for i, part in enumerate(parts[:-1]):
            if part in node and not isinstance(node[part], Config):
                raise ConfigError(
                    f"cannot set {dotted!r}: {'.'.join(parts[: i + 1])!r} is a "
                    f"scalar ({node[part]!r}), not a config section"
                )
            if part not in node:
                if not allow_new:
                    raise ConfigError(
                        f"override key {dotted!r} not in config (missing node "
                        f"{'.'.join(parts[: i + 1])!r}); prefix with + to add new keys"
                    )
                node[part] = Config()
            node = node[part]
        if not allow_new and parts[-1] not in node:
            raise ConfigError(
                f"override key {dotted!r} not in config; prefix with + to add new keys"
            )
        node[parts[-1]] = value


def _deep_merge(base: dict, override: dict) -> dict:
    out = dict(base)
    for key, value in override.items():
        if key in out and isinstance(out[key], dict) and isinstance(value, dict):
            out[key] = _deep_merge(out[key], value)
        else:
            out[key] = value
    return out


def _load_yaml_file(path: str) -> dict:
    with open(path) as f:
        data = yaml.safe_load(f) or {}
    if not isinstance(data, dict):
        raise ConfigError(f"{path} must contain a mapping, got {type(data).__name__}")
    return data


def _compose(conf_dir: str, config_name: str, group_choices: dict[str, str]) -> dict:
    """Compose a root config file with its ``defaults`` group list.

    Mirrors Hydra's composition: each ``defaults`` entry ``group: option``
    loads ``<conf_dir>/<group>/<option>.yaml`` and merges it under the group
    key — unless the file opts into the root namespace with the marker key
    ``_global_: true`` (our spelling of Hydra's ``@package _global_``, which
    every reference group file uses). A group file may additionally set
    ``_override_: true`` to merge AFTER the root config (the analogue of
    placing ``_self_`` first in a Hydra defaults list), letting a recipe
    file override root-level defaults like ``parameter.linear_schedule``.
    """
    root_path = os.path.join(conf_dir, f"{config_name}.yaml")
    root = _load_yaml_file(root_path)
    defaults = root.pop("defaults", [])
    merged: dict[str, Any] = {}
    post_root: dict[str, Any] = {}
    for entry in defaults:
        if isinstance(entry, str):  # bare entry: another root-level file
            merged = _deep_merge(merged, _compose(conf_dir, entry, group_choices))
            continue
        if not isinstance(entry, dict) or len(entry) != 1:
            raise ConfigError(f"bad defaults entry {entry!r} in {root_path}")
        (group, option), = entry.items()
        option = group_choices.get(group, option)
        path = os.path.join(conf_dir, group, f"{option}.yaml")
        if not os.path.exists(path):
            raise ConfigError(
                f"config group file not found: {path} (group {group!r}, option {option!r})"
            )
        group_data = _load_yaml_file(path)
        override = group_data.pop("_override_", False)
        if not group_data.pop("_global_", False):
            group_data = {group: group_data}
        if override:
            post_root = _deep_merge(post_root, group_data)
        else:
            merged = _deep_merge(merged, group_data)
    return _deep_merge(_deep_merge(merged, root), post_root)


def _parse_override_value(raw: str) -> Any:
    # YAML 1.1 requires a dot in floats, so safe_load('1e-4') is the STRING
    # '1e-4' — but reference recipes write decay=1e-4. Try numeric forms
    # first, then fall back to YAML typing (bools, null, lists, strings).
    stripped = raw.strip()
    try:
        return int(stripped)
    except ValueError:
        pass
    try:
        return float(stripped)
    except ValueError:
        pass
    try:
        return yaml.safe_load(raw)
    except yaml.YAMLError:
        return raw


def parse_overrides(
    argv: list[str], conf_dir: str | None = None
) -> tuple[dict[str, str], list[tuple[str, Any]]]:
    """Split ``group=option`` choices from ``a.b.c=value`` dotted overrides.

    A bare key (no dot) whose name matches a config group directory under
    ``conf_dir`` selects a group option, exactly like Hydra's
    ``experiment=cifar10``; everything else is a typed value override.
    """
    conf_dir = conf_dir or _CONF_DIR
    group_choices: dict[str, str] = {}
    value_overrides: list[tuple[str, Any]] = []
    for arg in argv:
        if "=" not in arg:
            raise ConfigError(
                f"override {arg!r} must look like key=value (e.g. parameter.epochs=200)"
            )
        key, raw = arg.split("=", 1)
        key = key.strip()
        if "." not in key and os.path.isdir(os.path.join(conf_dir, key.lstrip("+"))):
            group_choices[key.lstrip("+")] = raw.strip()
        else:
            value_overrides.append((key, _parse_override_value(raw)))
    return group_choices, value_overrides


def load_config(
    config_name: str,
    overrides: list[str] | None = None,
    conf_dir: str | None = None,
) -> Config:
    """Load ``<conf_dir>/<config_name>.yaml``, compose groups, apply overrides."""
    conf_dir = conf_dir or _CONF_DIR
    group_choices, value_overrides = parse_overrides(list(overrides or []), conf_dir)
    cfg = Config(_compose(conf_dir, config_name, group_choices))
    for dotted, value in value_overrides:
        if dotted.startswith("+"):
            cfg.update_dotted(dotted[1:], value, allow_new=True)
        else:
            cfg.update_dotted(dotted, value, allow_new=False)
    return cfg


def resolve_save_dir(cfg: Config, now: datetime.datetime | None = None) -> str:
    """Compute the run output directory.

    The reference relies on Hydra's auto-chdir into
    ``results/${experiment.name}/seed-${parameter.seed}/<date>/<time>``
    (``/root/reference/conf/hydra/output/custom.yaml:2-8``). We compute the
    same path but never chdir; callers pass it explicitly.
    """
    explicit = cfg.select("experiment.save_dir")
    if explicit:
        return str(explicit)
    now = now or datetime.datetime.now()
    return os.path.join(
        "results",
        str(cfg.experiment.name),
        f"seed-{cfg.parameter.seed}",
        now.strftime("%Y-%m-%d"),
        now.strftime("%H-%M-%S"),
    )


# ---------------------------------------------------------------------------
# Multirun sweeps — the reference's Hydra sweep surface
# (``/root/reference/conf/hydra/output/custom.yaml:6-8``: ``hydra.sweep.dir``
# + job-number subdirs). ``--multirun`` / ``-m`` on any entry point expands
# comma-list overrides into the cartesian product of jobs, each writing to
# ``<sweep_root>/<job_idx>``.
# ---------------------------------------------------------------------------

MULTIRUN_FLAGS = ("--multirun", "-m")


def split_multirun_flag(argv: list[str]) -> tuple[bool, list[str]]:
    """Strip Hydra's multirun flag from an argv-style override list."""
    multirun = any(a in MULTIRUN_FLAGS for a in argv)
    return multirun, [a for a in argv if a not in MULTIRUN_FLAGS]


def expand_sweep(argv: list[str]) -> list[list[str]]:
    """Expand ``key=v1,v2`` comma-list overrides into single-run combos.

    Mirrors Hydra's multirun semantics: every comma-listed override
    contributes one axis, and jobs are the cartesian product in argv order.
    A bracketed value (``key=[a,b]``) is one YAML list, not a sweep axis,
    and so is a quoted value (``key="a, b"`` — the shell strips nothing
    inside the quotes, so the comma is literal).

    ``experiment.save_dir`` may not be swept: :func:`run_multirun` overwrites
    every job's save_dir with ``<sweep_root>/<job_idx>``, so swept values
    would be silently discarded — rejected here instead.
    """
    import itertools

    axes: list[list[str]] = []
    for arg in argv:
        if "=" not in arg:
            raise ConfigError(
                f"override {arg!r} must look like key=value (e.g. parameter.epochs=200)"
            )
        key, raw = arg.split("=", 1)
        stripped = raw.strip()
        quoted = (
            len(stripped) >= 2
            and stripped[0] in "'\""
            and stripped[-1] == stripped[0]
        )
        if "," in stripped and not quoted and not stripped.startswith("["):
            if key.strip().lstrip("+") == "experiment.save_dir":
                raise ConfigError(
                    f"experiment.save_dir cannot be a sweep axis ({arg!r}): "
                    "multirun assigns each job <sweep_root>/<job_idx> and "
                    "would silently ignore the swept values; set a single "
                    "experiment.save_dir as the sweep root instead"
                )
            values = [v.strip() for v in stripped.split(",")]
            if any(not v for v in values):
                raise ConfigError(f"empty value in sweep override {arg!r}")
            axes.append([f"{key}={v}" for v in values])
        else:
            axes.append([arg])
    return [list(combo) for combo in itertools.product(*axes)]


def run_multirun(run_fn, config_name: str, argv: list[str]) -> list:
    """Run ``run_fn(cfg)`` once per sweep job, sequentially.

    Every job writes under one sweep root in a ``<job_idx>`` subdir, the
    analogue of Hydra's ``hydra.sweep.dir``/``subdir`` layout. The root is
    an explicit ``experiment.save_dir`` when given; otherwise a NEUTRAL
    dated ``results/multirun/...`` dir — job 0's own resolved save dir
    would encode job 0's name/seed in the path and misattribute the other
    jobs' results (e.g. a ``parameter.seed=3,5`` sweep filing seed-5 under
    ``seed-3/``). Returns the per-job results in job order.
    """
    import logging

    combos = expand_sweep(argv)
    sweep_root: str | None = None
    results = []
    for i, combo in enumerate(combos):
        cfg = load_config(config_name, overrides=combo)
        if sweep_root is None:
            explicit = cfg.select("experiment.save_dir")
            if explicit:
                sweep_root = str(explicit)
            else:
                import jax

                if jax.process_count() > 1:
                    raise ConfigError(
                        "multirun without an explicit experiment.save_dir is "
                        "not multi-process safe: each process would compute "
                        "its own dated sweep root and the ranks would "
                        "desynchronize; set experiment.save_dir to a shared "
                        "directory"
                    )
                now = datetime.datetime.now()
                sweep_root = os.path.join(
                    "results", "multirun",
                    now.strftime("%Y-%m-%d"), now.strftime("%H-%M-%S"),
                )
        cfg.update_dotted(
            "experiment.save_dir", os.path.join(sweep_root, str(i)), allow_new=True
        )
        logging.getLogger("simclr_tpu").info(
            "multirun job %d/%d: %s -> %s", i + 1, len(combos),
            " ".join(combo) or "<defaults>", cfg.experiment.save_dir,
        )
        results.append(run_fn(cfg))
    return results


# ---------------------------------------------------------------------------
# Startup validation — the reference's hand-rolled asserts, kept as explicit
# contracts (main.py:39-50, eval.py:20-28, supervised.py:18-27,
# save_features.py:15-17 in /root/reference).
# ---------------------------------------------------------------------------

def _require(cond: bool, message: str) -> None:
    if not cond:
        raise ConfigError(message)


def _supported_cnns() -> tuple[str, ...]:
    """Architectures from the single-source zoo table (models/arch.py)."""
    from simclr_tpu.models.arch import STAGE_SIZES

    return tuple(sorted(STAGE_SIZES))


def check_pretrain_conf(cfg: Config) -> None:
    p = cfg.parameter
    _require(p.epochs > 0, "parameter.epochs must be positive")
    _require(0 < p.temperature, "parameter.temperature must be positive")
    _require(p.d > 0, "parameter.d (projection dim) must be positive")
    _require(p.warmup_epochs >= 0, "parameter.warmup_epochs must be >= 0")
    _require(p.warmup_epochs <= p.epochs, "warmup_epochs must be <= epochs")
    _require(0.0 <= p.momentum <= 1.0, "parameter.momentum must be in [0, 1]")
    e = cfg.experiment
    _require(e.batches > 0, "experiment.batches (per-device batch) must be positive")
    _require(e.lr > 0, "experiment.lr must be positive")
    _require(e.decay >= 0, "experiment.decay must be >= 0")
    _require(0.0 <= e.strength <= 1.0, "experiment.strength must be in [0, 1]")
    from simclr_tpu.models.arch import ENCODERS, SEQUENCE_ENCODERS

    _require(
        e.base_cnn in ENCODERS,
        f"experiment.base_cnn must be {'|'.join(ENCODERS)}, got {e.base_cnn!r}",
    )
    if e.base_cnn in SEQUENCE_ENCODERS:
        _check_documents_conf(cfg)
    else:
        _require(
            e.name in ("cifar10", "cifar100"),
            f"experiment.name must be cifar10|cifar100 for the image encoder "
            f"{e.base_cnn!r}, got {e.name!r}",
        )
    _require(
        cfg.select("loss.negatives", "global") in ("global", "local", "ring"),
        "loss.negatives must be 'global', 'local', or 'ring'",
    )
    _check_runtime_conf(cfg)


def _check_documents_conf(cfg: Config) -> None:
    """A sequence encoder trains on token documents (``data/documents.py``),
    whose views are span crops packed into rows (``experiment=smallthinker-docs``
    has the keys)."""
    e = cfg.experiment
    _require(
        e.name == "synthetic-docs",
        f"the sequence encoder {e.base_cnn!r} takes token documents: "
        f"experiment.name must be synthetic-docs, got {e.name!r} "
        "(experiment=smallthinker-docs sets the group)",
    )
    crops = cfg.select("experiment.crop_lengths")
    _require(
        isinstance(crops, list) and crops
        and all(isinstance(n, int) and n > 0 for n in crops),
        f"experiment.crop_lengths must list a row's crop lengths, got {crops!r}",
    )
    _require(
        e.batches % len(crops) == 0,
        f"experiment.batches ({e.batches}) must fill rows of {len(crops)} crops",
    )
    _require(
        0 < max(crops) <= int(e.document_tokens) and int(e.documents) > 0,
        "experiment.document_tokens must hold the longest crop, and "
        "experiment.documents be positive",
    )
    _require(
        int(cfg.select("experiment.eval_every", 0) or 0) == 0,
        "experiment.eval_every probes image classes; a sequence encoder has none",
    )


def _check_runtime_conf(cfg: Config) -> None:
    # the one true universe lives in ops/augment_pallas.AUGMENT_IMPLS; the
    # import is lazy so merely validating a config stays jax-free
    impl = cfg.select("runtime.augment_impl", "xla")
    from simclr_tpu.ops.augment_pallas import AUGMENT_IMPLS

    _require(
        impl in AUGMENT_IMPLS,
        f"runtime.augment_impl must be {'|'.join(AUGMENT_IMPLS)}, "
        f"got {impl!r}",
    )
    _check_parallel_conf(cfg)
    _check_supervisor_conf(cfg)
    _check_telemetry_conf(cfg)


def check_telemetry_conf(cfg: Config) -> None:
    """Validate the ``telemetry.*`` knobs (run observability,
    docs/OBSERVABILITY.md). Called by both training entry points via
    :func:`_check_runtime_conf` and by the supervisor runner — like the
    supervisor knobs, a bad value fails at startup on either side of the
    process boundary. Deliberately jax-free."""
    _check_telemetry_conf(cfg)


def _check_telemetry_conf(cfg: Config) -> None:
    port = cfg.select("telemetry.port", 0)
    _require(
        isinstance(port, int) and not isinstance(port, bool)
        and 0 <= port <= 65535,
        f"telemetry.port must be an int in [0, 65535] (0 = exporter "
        f"disabled unless telemetry.ready_file is set), got {port!r}",
    )
    trace_max_ms = cfg.select("telemetry.trace_max_ms", 60000)
    _require(
        isinstance(trace_max_ms, (int, float)) and not isinstance(trace_max_ms, bool)
        and 0 < trace_max_ms <= 600000,
        "telemetry.trace_max_ms must be in (0, 600000] milliseconds "
        f"(cap for POST /debug/trace?ms=N), got {trace_max_ms!r}",
    )
    events = cfg.select("telemetry.events", True)
    _require(
        isinstance(events, bool),
        f"telemetry.events must be a boolean (true|false), got {events!r}",
    )
    anomaly = cfg.select("telemetry.anomaly", True)
    _require(
        isinstance(anomaly, bool),
        f"telemetry.anomaly must be a boolean (true|false), got {anomaly!r}",
    )
    warmup = cfg.select("telemetry.anomaly_warmup", 8)
    _require(
        isinstance(warmup, int) and not isinstance(warmup, bool)
        and 2 <= warmup <= 10000,
        "telemetry.anomaly_warmup must be an int in [2, 10000] step samples "
        f"before the detector classifies anything, got {warmup!r}",
    )
    slow_factor = cfg.select("telemetry.slow_step_factor", 4.0)
    _require(
        isinstance(slow_factor, (int, float)) and not isinstance(slow_factor, bool)
        and 1 <= slow_factor <= 1000,
        "telemetry.slow_step_factor must be in [1, 1000] MAD multiples over "
        f"the rolling median, got {slow_factor!r}",
    )
    stall_factor = cfg.select("telemetry.stall_factor", 10.0)
    _require(
        isinstance(stall_factor, (int, float)) and not isinstance(stall_factor, bool)
        and 1 <= stall_factor <= 1000,
        "telemetry.stall_factor must be in [1, 1000] multiples of the median "
        f"step time (stall watchdog deadline), got {stall_factor!r}",
    )
    stall_min = cfg.select("telemetry.stall_min_s", 2.0)
    _require(
        isinstance(stall_min, (int, float)) and not isinstance(stall_min, bool)
        and 0 < stall_min <= 3600,
        "telemetry.stall_min_s must be in (0, 3600] seconds (floor on the "
        f"stall watchdog deadline), got {stall_min!r}",
    )
    auto_trace = cfg.select("telemetry.auto_trace", False)
    _require(
        isinstance(auto_trace, bool),
        f"telemetry.auto_trace must be a boolean (true|false), got {auto_trace!r}",
    )
    auto_trace_ms = cfg.select("telemetry.auto_trace_ms", 500)
    _require(
        isinstance(auto_trace_ms, (int, float))
        and not isinstance(auto_trace_ms, bool)
        and 0 < auto_trace_ms <= 60000,
        "telemetry.auto_trace_ms must be in (0, 60000] milliseconds per "
        f"automatic capture, got {auto_trace_ms!r}",
    )
    cooldown = cfg.select("telemetry.auto_trace_cooldown_s", 300.0)
    _require(
        isinstance(cooldown, (int, float)) and not isinstance(cooldown, bool)
        and 0 <= cooldown <= 86400,
        "telemetry.auto_trace_cooldown_s must be in [0, 86400] seconds "
        f"between automatic captures, got {cooldown!r}",
    )
    auto_trace_max = cfg.select("telemetry.auto_trace_max", 3)
    _require(
        isinstance(auto_trace_max, int) and not isinstance(auto_trace_max, bool)
        and 1 <= auto_trace_max <= 100,
        "telemetry.auto_trace_max must be an int in [1, 100] automatic "
        f"captures per attempt, got {auto_trace_max!r}",
    )
    compile_sentry = cfg.select("telemetry.compile_sentry", True)
    _require(
        isinstance(compile_sentry, bool),
        f"telemetry.compile_sentry must be a boolean (true|false), "
        f"got {compile_sentry!r}",
    )
    hbm = cfg.select("telemetry.hbm", True)
    _require(
        isinstance(hbm, bool),
        f"telemetry.hbm must be a boolean (true|false), got {hbm!r}",
    )
    fleet = cfg.select("telemetry.fleet", False)
    _require(
        isinstance(fleet, bool),
        f"telemetry.fleet must be a boolean (true|false), got {fleet!r}",
    )
    fleet_port = cfg.select("telemetry.fleet_port", 0)
    _require(
        isinstance(fleet_port, int) and not isinstance(fleet_port, bool)
        and 0 <= fleet_port <= 65535,
        "telemetry.fleet_port must be an int in [0, 65535] (0 = ephemeral, "
        f"published via the fleet ready file), got {fleet_port!r}",
    )
    fleet_poll = cfg.select("telemetry.fleet_poll_s", 2.0)
    _require(
        isinstance(fleet_poll, (int, float)) and not isinstance(fleet_poll, bool)
        and 0 < fleet_poll <= 3600,
        "telemetry.fleet_poll_s must be in (0, 3600] seconds between fleet "
        f"scrape passes, got {fleet_poll!r}",
    )
    fleet_stale = cfg.select("telemetry.fleet_stale_after_s", 30.0)
    _require(
        isinstance(fleet_stale, (int, float)) and not isinstance(fleet_stale, bool)
        and 0 < fleet_stale <= 86400,
        "telemetry.fleet_stale_after_s must be in (0, 86400] seconds before "
        f"a silent host is gauged stale, got {fleet_stale!r}",
    )


def check_supervisor_conf(cfg: Config) -> None:
    """Validate the ``supervisor.*`` knobs (fault-tolerance policy,
    docs/FAULT_TOLERANCE.md). Called by the supervisor runner before it
    spawns anything, and by both training entry points via
    :func:`_check_runtime_conf` — a bad knob fails at startup on either side
    of the process boundary. Deliberately jax-free: the runner validates
    without touching any accelerator state."""
    _check_supervisor_conf(cfg)


def _check_supervisor_conf(cfg: Config) -> None:
    max_restarts = cfg.select("supervisor.max_restarts", 8)
    _require(
        isinstance(max_restarts, int) and 0 <= max_restarts <= 1000,
        f"supervisor.max_restarts must be an int in [0, 1000], got {max_restarts!r}",
    )
    backoff = cfg.select("supervisor.backoff_base_s", 5.0)
    _require(
        isinstance(backoff, (int, float)) and 0 <= backoff <= 3600,
        f"supervisor.backoff_base_s must be in [0, 3600] seconds, got {backoff!r}",
    )
    backoff_max = cfg.select("supervisor.backoff_max_s", 300.0)
    _require(
        isinstance(backoff_max, (int, float)) and 0 <= backoff_max <= 86400,
        f"supervisor.backoff_max_s must be in [0, 86400] seconds, "
        f"got {backoff_max!r}",
    )
    _require(
        backoff_max >= backoff,
        f"supervisor.backoff_max_s ({backoff_max!r}) must be >= "
        f"supervisor.backoff_base_s ({backoff!r}) — a cap below the base "
        "delay would make every restart wait the cap",
    )
    grow_back = cfg.select("supervisor.grow_back_cooldown_s", 60.0)
    _require(
        isinstance(grow_back, (int, float)) and 0 <= grow_back <= 86400,
        f"supervisor.grow_back_cooldown_s must be in [0, 86400] seconds, "
        f"got {grow_back!r}",
    )
    factor = cfg.select("supervisor.heartbeat_timeout_factor", 10.0)
    _require(
        isinstance(factor, (int, float)) and 1 <= factor <= 1000,
        "supervisor.heartbeat_timeout_factor must be in [1, 1000] "
        f"(multiples of the observed step time), got {factor!r}",
    )
    min_timeout = cfg.select("supervisor.heartbeat_min_timeout_s", 30.0)
    _require(
        isinstance(min_timeout, (int, float)) and 0 < min_timeout <= 86400,
        "supervisor.heartbeat_min_timeout_s must be in (0, 86400] seconds, "
        f"got {min_timeout!r}",
    )
    grace = cfg.select("supervisor.startup_grace_s", 600.0)
    _require(
        isinstance(grace, (int, float)) and 0 < grace <= 86400,
        f"supervisor.startup_grace_s must be in (0, 86400] seconds, got {grace!r}",
    )
    nan_budget = cfg.select("supervisor.nan_retry_budget", 2)
    _require(
        isinstance(nan_budget, int) and 0 <= nan_budget <= 100,
        f"supervisor.nan_retry_budget must be an int in [0, 100], got {nan_budget!r}",
    )


def _check_parallel_conf(cfg: Config) -> None:
    # single source of truth for the valid sets/ranges: parallel/compress.py
    from simclr_tpu.parallel.compress import (
        COMM_OVERLAP_MODES,
        DEFAULT_COMM_CHUNKS,
        GRAD_ALLREDUCE_MODES,
        MAX_COMM_CHUNKS,
        normalize_overlap,
    )

    mode = cfg.select("parallel.grad_allreduce", "exact")
    _require(
        mode in GRAD_ALLREDUCE_MODES,
        f"parallel.grad_allreduce must be one of {GRAD_ALLREDUCE_MODES}, "
        f"got {mode!r}",
    )
    overlap = normalize_overlap(cfg.select("parallel.comm_overlap", "off"))
    _require(
        overlap in COMM_OVERLAP_MODES,
        f"parallel.comm_overlap must be one of {COMM_OVERLAP_MODES}, "
        f"got {overlap!r}",
    )
    chunks = cfg.select("parallel.comm_chunks", DEFAULT_COMM_CHUNKS)
    _require(
        isinstance(chunks, int) and not isinstance(chunks, bool)
        and 1 <= chunks <= MAX_COMM_CHUNKS,
        f"parallel.comm_chunks must be an int in [1, {MAX_COMM_CHUNKS}], "
        f"got {chunks!r}",
    )


def check_eval_conf(cfg: Config) -> None:
    p = cfg.parameter
    _require(p.epochs >= 0, "parameter.epochs must be >= 0")
    _require(p.top_k > 0, "parameter.top_k must be positive")
    _require(
        p.classifier in ("centroid", "linear", "nonlinear"),
        f"parameter.classifier must be centroid|linear|nonlinear, got {p.classifier!r}",
    )
    _require(bool(cfg.experiment.target_dir), "experiment.target_dir must be set")
    _require(cfg.experiment.target_dir != "DUMMY-PATH", "experiment.target_dir must be set")


def check_supervised_conf(cfg: Config) -> None:
    p = cfg.parameter
    _require(p.epochs > 0, "parameter.epochs must be positive")
    _require(p.metric in ("loss", "acc"), "parameter.metric must be loss|acc")
    _require(p.warmup_epochs >= 0, "parameter.warmup_epochs must be >= 0")
    _check_runtime_conf(cfg)


def check_save_features_conf(cfg: Config) -> None:
    _require(bool(cfg.experiment.target_dir), "experiment.target_dir must be set")
    _require(cfg.experiment.target_dir != "DUMMY-PATH", "experiment.target_dir must be set")


def check_serve_conf(
    cfg: Config, *, require_checkpoint_source: bool = True
) -> None:
    s = cfg.select("serve")
    _require(s is not None, "serve config group missing (load_config('serve'))")
    _require(int(s.max_batch) > 0, "serve.max_batch must be positive")
    _require(float(s.max_delay_ms) >= 0, "serve.max_delay_ms must be >= 0")
    _require(int(s.queue_depth) > 0, "serve.queue_depth must be positive")
    _require(float(s.request_timeout_s) > 0, "serve.request_timeout_s must be positive")
    _require(0 <= int(s.port) <= 65535, "serve.port must be in [0, 65535]")
    rate = cfg.select("serve.trace_sample_rate", 0.0)
    _require(
        isinstance(rate, (int, float)) and not isinstance(rate, bool)
        and 0.0 <= rate <= 1.0,
        "serve.trace_sample_rate must be in [0.0, 1.0] (fraction of request "
        f"traces sampled into serve.requests_log), got {rate!r}",
    )
    requests_log = cfg.select("serve.requests_log")
    _require(
        requests_log is None or isinstance(requests_log, str),
        "serve.requests_log must be a path string or null (null = no "
        f"sidecar), got {requests_log!r}",
    )
    replicas = cfg.select("serve.replicas", -1)
    _require(
        isinstance(replicas, int) and not isinstance(replicas, bool)
        and (replicas == -1 or replicas >= 1),
        "serve.replicas must be -1 (one replica per local device) or a "
        f"positive int, got {replicas!r}",
    )
    # mirrors parallel.compress.WEIGHT_QUANT_MODES; inlined because this
    # module is deliberately jax-free
    weights = cfg.select("serve.weights", "exact")
    _require(
        weights in ("exact", "bf16", "int8"),
        f"serve.weights must be exact|bf16|int8, got {weights!r}",
    )
    corpus = cfg.select("serve.corpus")
    _require(
        corpus is None or isinstance(corpus, str),
        "serve.corpus must be an (n, d) .npy/.npz path or null (null = "
        f"no /v1/neighbors), got {corpus!r}",
    )
    k = cfg.select("serve.neighbors_k", 10)
    _require(
        isinstance(k, int) and not isinstance(k, bool) and k >= 1,
        f"serve.neighbors_k must be an int >= 1, got {k!r}",
    )
    metric = cfg.select("serve.neighbors_metric", "dot")
    _require(
        metric in ("dot", "cosine"),
        f"serve.neighbors_metric must be dot|cosine, got {metric!r}",
    )
    # mirrors parallel.compress.CORPUS_DTYPE_MODES (jax-free module, same
    # reason serve.weights inlines its set)
    corpus_dtype = cfg.select("serve.corpus_dtype", "fp32")
    _require(
        corpus_dtype in ("fp32", "int8"),
        f"serve.corpus_dtype must be fp32|int8, got {corpus_dtype!r}",
    )
    ann_cells = cfg.select("serve.ann_cells", 0)
    _require(
        isinstance(ann_cells, int) and not isinstance(ann_cells, bool)
        and 0 <= ann_cells <= 65536,
        "serve.ann_cells must be an int in [0, 65536] (IVF cells per shard; "
        f"0 = exact scan), got {ann_cells!r}",
    )
    ann_probe = cfg.select("serve.ann_probe", 1)
    _require(
        isinstance(ann_probe, int) and not isinstance(ann_probe, bool)
        and ann_probe >= 1,
        f"serve.ann_probe must be an int >= 1 (cells scored per query), "
        f"got {ann_probe!r}",
    )
    _require(
        ann_cells == 0 or ann_probe <= ann_cells,
        f"serve.ann_probe must be <= serve.ann_cells ({ann_cells}) when the "
        f"IVF scan is on, got {ann_probe!r}",
    )
    # one of the checkpoint sources must be real — except under the
    # co-scheduler, which serves random generation-0 weights and hot-reloads
    # checkpoints as training writes them (check_cosched_conf)
    if require_checkpoint_source and not s.get("checkpoint"):
        _require(
            bool(cfg.experiment.target_dir)
            and cfg.experiment.target_dir != "DUMMY-PATH",
            "set experiment.target_dir (checkpoint run dir) or serve.checkpoint",
        )


def check_cosched_conf(cfg: Config) -> None:
    """Validate the co-scheduler surface (``cosched.*`` plus the serve,
    supervisor, and telemetry knobs it composes — ``conf/cosched.yaml``).
    The serve tier starts on random generation-0 weights and hot-reloads
    each checkpoint the training run writes, so unlike the standalone
    server no pre-existing checkpoint source is required."""
    check_serve_conf(cfg, require_checkpoint_source=False)
    _check_supervisor_conf(cfg)
    _check_telemetry_conf(cfg)
    c = cfg.select("cosched")
    _require(c is not None, "cosched config group missing (load_config('cosched'))")
    serve_devices = cfg.select("cosched.serve_devices", 1)
    _require(
        isinstance(serve_devices, int) and not isinstance(serve_devices, bool)
        and serve_devices >= 1,
        "cosched.serve_devices must be an int >= 1 (local devices reserved "
        f"for the serve tier), got {serve_devices!r}",
    )
    max_serve = cfg.select("cosched.max_serve_devices", serve_devices)
    _require(
        isinstance(max_serve, int) and not isinstance(max_serve, bool)
        and max_serve >= serve_devices,
        "cosched.max_serve_devices must be an int >= cosched.serve_devices "
        f"(ceiling the elastic grow can reach), got {max_serve!r}",
    )
    poll = cfg.select("cosched.reload_poll_s", 2.0)
    _require(
        isinstance(poll, (int, float)) and not isinstance(poll, bool)
        and 0 < poll <= 3600,
        f"cosched.reload_poll_s must be in (0, 3600] seconds between "
        f"checkpoint-watch passes, got {poll!r}",
    )
    corpus_images = cfg.select("cosched.corpus_images", 0)
    _require(
        isinstance(corpus_images, int) and not isinstance(corpus_images, bool)
        and 0 <= corpus_images <= 1_000_000,
        "cosched.corpus_images must be an int in [0, 1000000] retrieval "
        f"corpus rows (0 = no /v1/neighbors), got {corpus_images!r}",
    )
    reembed = cfg.select("cosched.reembed_batch", 256)
    _require(
        isinstance(reembed, int) and not isinstance(reembed, bool)
        and 1 <= reembed <= 4096,
        f"cosched.reembed_batch must be an int in [1, 4096] rows per "
        f"re-embed forward, got {reembed!r}",
    )
    realloc = cfg.select("cosched.reallocation", True)
    _require(
        isinstance(realloc, bool),
        f"cosched.reallocation must be a boolean (true|false), got {realloc!r}",
    )
    high = cfg.select("cosched.pressure_high", 0.75)
    low = cfg.select("cosched.pressure_low", 0.1)
    for name, v in (("pressure_high", high), ("pressure_low", low)):
        _require(
            isinstance(v, (int, float)) and not isinstance(v, bool)
            and 0.0 <= v <= 1.0,
            f"cosched.{name} must be in [0.0, 1.0] (fraction of "
            f"serve.queue_depth), got {v!r}",
        )
    _require(
        low < high,
        f"cosched.pressure_low ({low!r}) must be < cosched.pressure_high "
        f"({high!r}) — the hysteresis band cannot be empty",
    )
    sustain = cfg.select("cosched.pressure_sustain_s", 10.0)
    _require(
        isinstance(sustain, (int, float)) and not isinstance(sustain, bool)
        and 0 <= sustain <= 3600,
        "cosched.pressure_sustain_s must be in [0, 3600] seconds of "
        f"sustained pressure before reallocating, got {sustain!r}",
    )
    cooldown = cfg.select("cosched.realloc_cooldown_s", 30.0)
    _require(
        isinstance(cooldown, (int, float)) and not isinstance(cooldown, bool)
        and 0 <= cooldown <= 86400,
        "cosched.realloc_cooldown_s must be in [0, 86400] seconds between "
        f"reallocation direction changes, got {cooldown!r}",
    )
