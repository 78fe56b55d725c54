"""Core data model for the static-analysis suite.

Everything here is stdlib-only (ast/re/pathlib): the linter must stay
runnable in a bare CI venv and as a pre-commit hook without touching
jax. Rules receive parsed `ModuleSource` objects (one shared AST per
file) and a `Settings` instance that carries every repo-specific knob —
fixture tests swap in a Settings pointing at a miniature tree, so no
rule hard-codes a path.
"""
from __future__ import annotations

import ast
import dataclasses
import fnmatch
import io
import pathlib
import re
import tokenize
from typing import Dict, Iterable, Iterator, List, Mapping, Optional, Tuple

# ---------------------------------------------------------------------------
# Violation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Violation:
    """One finding: `file:line`, rule id, message, and a fix hint."""

    rule: str
    path: str  # repo-relative posix path
    line: int  # 1-based
    message: str
    hint: str = ""
    # The stripped source line, used as the location-stable baseline
    # fingerprint (line numbers drift; the offending text does not).
    context: str = ""

    def format(self, show_hint: bool = True) -> str:
        out = f"{self.path}:{self.line}: [{self.rule}] {self.message}"
        if show_hint and self.hint:
            out += f"\n    hint: {self.hint}"
        return out

    def fingerprint(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.context)

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Pragmas
# ---------------------------------------------------------------------------

# Inline suppression, written as a trailing (or preceding-line)
# comment: ``lint: allow(rule-a,rule-b) reason=...``. The reason is
# mandatory — an allow without a written justification is itself a
# violation (`bad-pragma`), so every suppression in the tree documents
# *why* the pattern is safe here.
PRAGMA_RE = re.compile(
    r"#\s*lint:\s*allow\(([^)]*)\)(?:\s+reason=(.+))?")


@dataclasses.dataclass
class Pragma:
    line: int
    rules: Tuple[str, ...]
    reason: str

    @property
    def valid(self) -> bool:
        return bool(self.rules) and bool(self.reason.strip())


def parse_pragmas(text: str) -> Dict[int, Pragma]:
    """1-based line -> Pragma for every lint-allow comment.

    Tokenize-based so only real COMMENT tokens count — a docstring that
    *mentions* the pragma syntax is not a pragma. Falls back to a plain
    line scan when the file does not tokenize (the parse-error path)."""
    pragmas: Dict[int, Pragma] = {}

    def record(line: int, comment: str) -> None:
        match = PRAGMA_RE.search(comment)
        if match is None:
            return
        rules = tuple(r.strip() for r in match.group(1).split(",")
                      if r.strip())
        pragmas[line] = Pragma(line=line, rules=rules,
                               reason=(match.group(2) or "").strip())

    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                record(tok.start[0], tok.string)
    except (tokenize.TokenError, SyntaxError, IndentationError):
        for i, line in enumerate(text.splitlines(), start=1):
            record(i, line)
    return pragmas


# ---------------------------------------------------------------------------
# Settings (repo-specific rule configuration)
# ---------------------------------------------------------------------------

# Functions on the engine step loop where an implicit device->host sync
# is a tail-latency bug: every `jax.block_until_ready` / `device_get` /
# `.item()` / `np.asarray` there must be the *intentional* fetch point
# (pragma with a reason) or a bug. Patterns are fnmatch'd against both
# the bare and the `Class.method` qualified name.
DEFAULT_HOT_PATHS: Mapping[str, Tuple[str, ...]] = {
    "intellillm_tpu/worker/model_runner.py": (
        "InflightStep._finalize", "InflightStep.finalize",
        "ModelRunner.execute_model", "ModelRunner._execute_mixed",
        "ModelRunner.execute_decode_cont",
        "ModelRunner.execute_model_teacher",
        "ModelRunner._guarded_call",
    ),
    "intellillm_tpu/layers/sampler.py": (
        "sample", "_apply_top_k_top_p_min_p", "apply_penalties",
    ),
    "intellillm_tpu/engine/llm_engine.py": (
        "LLMEngine.step", "LLMEngine.step_pipelined",
        "LLMEngine._process_model_outputs",
    ),
    "intellillm_tpu/worker/worker.py": (
        "Worker.execute_model", "Worker._warm_up*",
    ),
    "intellillm_tpu/worker/spec_decode/spec_worker.py": (
        "SpecDecodeWorker.execute_model",
        "SpecDecodeWorker._warm_teacher",
        "SpecDecodeWorker._warm_up*",
    ),
    "intellillm_tpu/worker/spec_decode/multi_step_worker.py": ("*", ),
}

# Functions that run under `jax.jit` tracing but are not themselves the
# wrap site (helpers called from inside a jitted body). The
# recompile-hazard rule treats them as traced code.
DEFAULT_EXTRA_TRACED: Mapping[str, Tuple[str, ...]] = {
    "intellillm_tpu/layers/sampler.py": (
        "sample", "_apply_top_k_top_p_min_p", "apply_penalties",
    ),
}

# Jit dispatch-bucket axes a module may define (attributes/globals named
# `*_buckets`): each axis multiplies the executable count (one XLA
# compile per bucket combination). model_runner.py collapsed to the
# single mixed `(token_budget,)` family in PR 12 — the recompile-hazard
# rule fails any NEW `*_buckets` definition there so the bucket zoo
# (batch x length x block-width, 5-executable warm-up) cannot quietly
# come back.
DEFAULT_BUCKET_AXES: Mapping[str, Tuple[str, ...]] = {
    "intellillm_tpu/worker/model_runner.py": ("mixed_token_buckets", ),
}

# Modules allowed to construct Prometheus collectors. Everything else
# reporting a metric goes through these (one registry, one reset hook,
# one docs table) — ad-hoc families elsewhere dodge the hygiene guards.
DEFAULT_METRICS_MODULES: Tuple[str, ...] = (
    "intellillm_tpu/obs/*.py",
    "intellillm_tpu/engine/metrics.py",
    "intellillm_tpu/router/metrics.py",
    "intellillm_tpu/prediction/metrics.py",
    "intellillm_tpu/worker/spec_decode/metrics.py",
    "intellillm_tpu/tenancy/metrics.py",
)

# Per-request server paths where an append to a module-level container
# is unbounded growth (one entry per request, nothing evicts).
DEFAULT_REQUEST_PATH_GLOBS: Tuple[str, ...] = (
    "intellillm_tpu/entrypoints/*.py",
    "intellillm_tpu/entrypoints/openai/*.py",
    "intellillm_tpu/router/server.py",
    "intellillm_tpu/engine/async_llm_engine.py",
)

# Argparse surfaces whose post-seed flags must be documented (moved
# verbatim from tests/obs/test_flag_docs.py, which is now a wrapper).
DEFAULT_FLAG_SOURCES: Tuple[str, ...] = (
    "intellillm_tpu/engine/arg_utils.py",
    "intellillm_tpu/entrypoints/api_server.py",
    "intellillm_tpu/entrypoints/openai/api_server.py",
    "intellillm_tpu/router/server.py",
)

# The EngineArgs/server flags present in the growth seed (commit
# 47dbfda). Anything NOT in this set was added by a later PR and must
# be documented. Frozen on purpose: extend it only if a seed flag was
# genuinely missed, never to dodge documenting a new flag.
DEFAULT_SEED_FLAGS = frozenset({
    "--block-size", "--chat-template", "--data-parallel-size",
    "--disable-log-requests", "--disable-log-stats", "--dtype",
    "--enable-lora", "--enforce-eager", "--gpu-memory-utilization",
    "--hbm-utilization", "--host", "--kv-cache-dtype", "--load-format",
    "--lora-dtype", "--lora-extra-vocab-size", "--max-cpu-loras",
    "--max-log-len", "--max-lora-rank", "--max-loras", "--max-model-len",
    "--max-num-batched-tokens", "--max-num-seqs", "--max-paddings",
    "--model", "--num-decode-steps", "--num-device-blocks-override",
    "--num-speculative-tokens", "--pipeline-parallel-size", "--port",
    "--quantization", "--response-role", "--revision",
    "--scheduling-policy", "--seed", "--served-model-name",
    "--speculative-model", "--swap-space",
    "--tensor-parallel-size", "--tokenizer", "--tokenizer-mode",
    "--trust-remote-code", "--api-key",
})

# Operator docs where flags / env vars / metric names must appear.
DEFAULT_DOC_FILES: Tuple[str, ...] = (
    "docs/observability.md",
    "docs/routing.md",
    "docs/scheduling.md",
    "docs/kernels.md",
)
DEFAULT_METRICS_DOC = "docs/observability.md"

# Env vars of the observability subsystem are operator-facing and
# belong in the docs/observability.md env table, and the kernel
# selection flags under ops/ belong in docs/kernels.md; packages
# outside these carry developer escape hatches that are deliberately
# undocumented.
DEFAULT_ENV_VAR_DIRS: Tuple[str, ...] = ("intellillm_tpu/obs",
                                         "intellillm_tpu/ops")

# Quoted intellillm_ literals that are not metric names (the package
# prefix itself, the request-id contextvar in logger.py).
DEFAULT_NON_METRICS = frozenset({"intellillm_request_id"})


@dataclasses.dataclass
class Settings:
    """Every repo-specific knob the rules read. Tests point repo_root at
    a fixture tree and override the mappings they exercise."""

    repo_root: pathlib.Path
    hot_paths: Mapping[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_HOT_PATHS))
    extra_traced: Mapping[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_EXTRA_TRACED))
    bucket_axes: Mapping[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_BUCKET_AXES))
    metrics_modules: Tuple[str, ...] = DEFAULT_METRICS_MODULES
    request_path_globs: Tuple[str, ...] = DEFAULT_REQUEST_PATH_GLOBS
    flag_sources: Tuple[str, ...] = DEFAULT_FLAG_SOURCES
    seed_flags: frozenset = DEFAULT_SEED_FLAGS
    doc_files: Tuple[str, ...] = DEFAULT_DOC_FILES
    metrics_doc: str = DEFAULT_METRICS_DOC
    env_var_dirs: Tuple[str, ...] = DEFAULT_ENV_VAR_DIRS
    non_metrics: frozenset = DEFAULT_NON_METRICS

    def metric_prefix(self) -> str:
        return "intellillm_"


# ---------------------------------------------------------------------------
# Module / Project
# ---------------------------------------------------------------------------


class ModuleSource:
    """One parsed Python file: text, lines, shared AST, pragmas."""

    def __init__(self, path: pathlib.Path, rel: str) -> None:
        self.path = path
        self.rel = rel
        self.text = path.read_text(encoding="utf-8")
        self.lines = self.text.splitlines()
        self.pragmas = parse_pragmas(self.text)
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree: Optional[ast.AST] = ast.parse(self.text)
        except SyntaxError as e:
            self.tree = None
            self.parse_error = e

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def matches(self, globs: Iterable[str]) -> bool:
        return any(fnmatch.fnmatch(self.rel, g) for g in globs)


class Project:
    """The scanned file set plus repo-level context for cross-file rules."""

    def __init__(self, settings: Settings,
                 modules: List[ModuleSource]) -> None:
        self.settings = settings
        self.modules = modules
        self.by_rel = {m.rel: m for m in modules}

    def read_rel(self, rel: str) -> Optional[str]:
        """Text of a repo file (docs etc.) that is not a scanned module."""
        mod = self.by_rel.get(rel)
        if mod is not None:
            return mod.text
        path = self.settings.repo_root / rel
        try:
            return path.read_text(encoding="utf-8")
        except OSError:
            return None


# ---------------------------------------------------------------------------
# Rule base + registry
# ---------------------------------------------------------------------------


class Rule:
    """A rule plug-in. Subclasses set `id`/`summary`/`hint` and override
    `check` (per parsed module) and/or `finalize` (cross-file, runs once
    after every module was checked)."""

    id: str = ""
    summary: str = ""
    hint: str = ""

    def __init__(self, settings: Settings) -> None:
        self.settings = settings

    def check(self, mod: ModuleSource) -> Iterator[Violation]:
        return iter(())

    def finalize(self, project: Project) -> Iterator[Violation]:
        return iter(())

    def violation(self, mod: Optional[ModuleSource], rel: str, line: int,
                  message: str, hint: str = "",
                  context: str = "") -> Violation:
        if not context and mod is not None:
            context = mod.line_text(line)
        return Violation(rule=self.id, path=rel, line=line, message=message,
                         hint=hint or self.hint, context=context)


_REGISTRY: Dict[str, type] = {}

# Rule ids that exist without a Rule subclass (engine-level checks);
# pragma validation accepts them.
ENGINE_RULE_IDS = ("bad-pragma", "parse-error")


def register_rule(cls: type) -> type:
    """Class decorator: adds the rule to the plug-in registry."""
    assert cls.id, cls
    assert cls.id not in _REGISTRY, f"duplicate rule id {cls.id}"
    _REGISTRY[cls.id] = cls
    return cls


def available_rules() -> Dict[str, type]:
    # Importing the rules package populates the registry.
    import intellillm_tpu.analysis.rules  # noqa: F401
    return dict(_REGISTRY)


def known_rule_ids() -> frozenset:
    return frozenset(available_rules()) | frozenset(ENGINE_RULE_IDS)


def build_rules(settings: Settings,
                only: Optional[Iterable[str]] = None) -> List[Rule]:
    registry = available_rules()
    ids = list(registry) if only is None else list(only)
    unknown = [i for i in ids if i not in registry]
    if unknown:
        raise ValueError(
            f"unknown rule id(s) {unknown}; available: {sorted(registry)}")
    return [registry[i](settings) for i in ids]
