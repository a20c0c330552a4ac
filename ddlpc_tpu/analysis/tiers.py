"""Module tier registry + transitive import-graph checker.

Every module under ``ddlpc_tpu`` declares the *import-time* dependency
surface it is allowed — in THIS file, so adding a module forces an
explicit tier decision in review:

- ``stdlib`` — stdlib + same-or-lower-tier ``ddlpc_tpu`` modules only.
  The telemetry substrate and the resilience protocol live here: they are
  importable in any thread, any process, with nothing installed.
- ``host`` — third-party host libraries (numpy, PIL, ...) allowed;
  ``jax``/``jaxlib``/``flax``/``optax`` forbidden, TRANSITIVELY.  This is
  the property that makes PR 9's fleet restart fast: the supervisor and
  routing tiers never pay an XLA import, so a replica relaunch is
  milliseconds of Python, not seconds of jax init.
- ``jax`` — the accelerator tier; anything goes.

The checker (:func:`check_tiers`) parses module-level imports with
``ast`` (imports inside functions are deliberate lazy escapes and do not
count — the runtime meta-path test in ``tests/test_analysis.py`` pins
that they stay lazy), adds the implicit parent-package edges (importing
``a.b.c`` executes ``a/__init__`` and ``a/b/__init__`` first), and walks
the closure.  A ``host``-tier module that can reach an ``import jax``
fails with the full chain, file:line of the offending import included.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

STDLIB, HOST, JAX = "stdlib", "host", "jax"
_RANK = {STDLIB: 0, HOST: 1, JAX: 2}

# Import roots forbidden below the jax tier.  ``jnp`` etc. are attributes
# of jax, so the root covers them.
JAX_ROOTS = frozenset({"jax", "jaxlib", "flax", "optax"})

# The one registry.  New modules must be added here explicitly — an
# undeclared module is a violation (rule ``tier-undeclared``), as is a
# declaration for a module that no longer exists.
MODULE_TIERS: Dict[str, str] = {
    "ddlpc_tpu": STDLIB,
    "ddlpc_tpu.config": STDLIB,
    # obs: everything except the profiling hooks is pure stdlib by
    # charter (obs/__init__.py docstring).
    "ddlpc_tpu.obs": STDLIB,
    "ddlpc_tpu.obs.schema": STDLIB,
    "ddlpc_tpu.obs.registry": STDLIB,
    "ddlpc_tpu.obs.tracing": STDLIB,
    "ddlpc_tpu.obs.health": STDLIB,
    "ddlpc_tpu.obs.http": STDLIB,
    "ddlpc_tpu.obs.flops": STDLIB,
    "ddlpc_tpu.obs.comm": STDLIB,
    "ddlpc_tpu.obs.hbm": STDLIB,
    "ddlpc_tpu.obs.profiling": STDLIB,  # jax reached lazily, per capture
    "ddlpc_tpu.obs.xplane": STDLIB,  # TF proto import is optional/lazy
    # fleet observability (ISSUE 14): the trace merger and the telemetry
    # aggregator run in router/CI processes — provably jax-free, like the
    # routing tier they serve.
    "ddlpc_tpu.obs.merge": STDLIB,
    "ddlpc_tpu.obs.aggregate": STDLIB,
    # lineage (ISSUE 17): checkpoint provenance records.  Stdlib by
    # charter — the jax-free router tier reads checkpoint sidecars
    # through it for the model-age gauge.
    "ddlpc_tpu.obs.lineage": STDLIB,
    # resilience: the supervisor must restart a crashed trainer without
    # importing what crashed it.
    "ddlpc_tpu.resilience": STDLIB,
    "ddlpc_tpu.resilience.protocol": STDLIB,
    "ddlpc_tpu.resilience.supervisor": STDLIB,
    "ddlpc_tpu.resilience.chaos": STDLIB,
    # analysis: the analyzer itself runs without jax.
    "ddlpc_tpu.analysis": STDLIB,
    "ddlpc_tpu.analysis.core": STDLIB,
    "ddlpc_tpu.analysis.rules": STDLIB,
    "ddlpc_tpu.analysis.tiers": STDLIB,
    "ddlpc_tpu.analysis.lockcheck": STDLIB,
    "ddlpc_tpu.analysis.lock_fixtures": HOST,  # exercises the serve tier
    # the HLO/jaxpr walkers are pure text/structure (jaxpr objects come
    # in as arguments); the program auditor builds and lowers the real
    # step programs, so it owns the full accelerator stack (its jax
    # imports stay function-local so the baseline validators import
    # cheaply from perf_gate --smoke).
    "ddlpc_tpu.analysis.hlo": STDLIB,
    "ddlpc_tpu.analysis.program": JAX,
    # serve: the routing/fleet tier is jax-free (numpy allowed — the
    # engine's host-side tiling math); engine compiles lazily.
    "ddlpc_tpu.serve": HOST,
    # batching's own code is stdlib, but importing it executes
    # serve/__init__ (numpy via the engine) — tier describes the runtime
    # import closure, parent packages included.
    "ddlpc_tpu.serve.batching": HOST,
    "ddlpc_tpu.serve.cbatch": HOST,
    "ddlpc_tpu.serve.metrics": HOST,
    "ddlpc_tpu.serve.engine": HOST,
    # quantized's own imports are lazy (jax at quantization time, like
    # obs/profiling) so the engine can import it without paying jax;
    # router/fleet stay provably jax-free either way.
    "ddlpc_tpu.serve.quantized": HOST,
    "ddlpc_tpu.serve.server": HOST,
    "ddlpc_tpu.serve.router": HOST,
    "ddlpc_tpu.serve.fleet": HOST,
    # elastic-fleet control plane (ISSUE 16): both are stdlib-only code,
    # HOST for the same parent-package reason as batching — proving the
    # autoscaler/cache never pay a jax import is the point of the tier.
    "ddlpc_tpu.serve.autoscale": HOST,
    "ddlpc_tpu.serve.cache": HOST,
    # utils: wire/fsio are stdlib; native needs numpy; compat configures
    # jax itself.  compile_cache reaches jax lazily so the jax-free serve
    # tier's server entry point can import it.
    "ddlpc_tpu.utils": STDLIB,
    "ddlpc_tpu.utils.wire": STDLIB,
    "ddlpc_tpu.utils.fsio": STDLIB,
    "ddlpc_tpu.utils.native": HOST,
    "ddlpc_tpu.utils.compat": JAX,
    "ddlpc_tpu.utils.compile_cache": STDLIB,
    # the accelerator tier
    "ddlpc_tpu.data": JAX,
    "ddlpc_tpu.data.datasets": JAX,
    "ddlpc_tpu.data.loader": JAX,
    "ddlpc_tpu.models": JAX,
    "ddlpc_tpu.models.layers": JAX,
    "ddlpc_tpu.models.unet": JAX,
    "ddlpc_tpu.models.unetpp": JAX,
    "ddlpc_tpu.models.deeplabv3p": JAX,
    "ddlpc_tpu.models.lfm2_moe": JAX,
    "ddlpc_tpu.models.keye_vl2": JAX,
    "ddlpc_tpu.models.olmo_hybrid": JAX,
    "ddlpc_tpu.models.deepseek_v3": JAX,
    "ddlpc_tpu.ops": JAX,
    "ddlpc_tpu.ops.losses": JAX,
    "ddlpc_tpu.ops.metrics": JAX,
    "ddlpc_tpu.ops.gated_delta": JAX,
    "ddlpc_tpu.ops.quantize": JAX,
    "ddlpc_tpu.ops.pallas_attention": JAX,
    "ddlpc_tpu.ops.pallas_gated_delta": JAX,
    "ddlpc_tpu.ops.pallas_quantize": JAX,
    "ddlpc_tpu.parallel": JAX,
    "ddlpc_tpu.parallel.mesh": JAX,
    "ddlpc_tpu.parallel.halo": JAX,
    # jax-free by construction (obs/comm and tooling compute bucket
    # assignments without the accelerator stack), but the implicit
    # parent-package edge pins it to the parallel package's tier.
    "ddlpc_tpu.parallel.bucketing": JAX,
    "ddlpc_tpu.parallel.grad_sync": JAX,
    "ddlpc_tpu.parallel.compressed_allreduce": JAX,
    "ddlpc_tpu.parallel.partition": JAX,
    "ddlpc_tpu.parallel.shard_update": JAX,
    "ddlpc_tpu.parallel.train_step": JAX,
    "ddlpc_tpu.train": JAX,
    "ddlpc_tpu.train.__main__": JAX,
    "ddlpc_tpu.train.trainer": JAX,
    "ddlpc_tpu.train.optim": JAX,
    "ddlpc_tpu.train.checkpoint": JAX,
    "ddlpc_tpu.train.async_checkpoint": JAX,
    "ddlpc_tpu.train.observability": JAX,
    "ddlpc_tpu.train.watchdog": JAX,
    "ddlpc_tpu.predict": JAX,
}

_STDLIB_NAMES = frozenset(sys.stdlib_module_names) | {"__future__"}


def discover_modules(pkg_dir: str) -> Dict[str, str]:
    """``ddlpc_tpu.x.y`` module name -> file path under ``pkg_dir``."""
    out: Dict[str, str] = {}
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, os.path.dirname(pkg_dir))
            parts = rel[:-3].split(os.sep)
            if parts[-1] == "__init__":
                parts = parts[:-1]
            out[".".join(parts)] = path
    return out


def _toplevel_imports(
    tree: ast.Module, module: str, is_pkg: bool
) -> List[Tuple[str, int]]:
    """(imported module name, lineno) for every module-level import.

    ``if TYPE_CHECKING:`` blocks never execute — skipped.  ``try:`` /
    ``if:`` bodies at module level DO execute — included.
    """
    out: List[Tuple[str, int]] = []

    def visit_body(body) -> None:
        for node in body:
            if isinstance(node, ast.Import):
                out.extend((a.name, node.lineno) for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = module.split(".")
                    if not is_pkg:
                        base = base[:-1]
                    base = base[: len(base) - (node.level - 1)]
                    prefix = ".".join(base)
                    mod = (
                        f"{prefix}.{node.module}" if node.module else prefix
                    )
                else:
                    mod = node.module or ""
                if mod:
                    out.append((mod, node.lineno))
                    # `from pkg import name` may bind a SUBMODULE: record
                    # the candidate; the resolver keeps it only if it
                    # exists as a module.
                    for a in node.names:
                        if a.name != "*":
                            out.append((f"{mod}.{a.name}", node.lineno))
            elif isinstance(node, ast.If):
                test = node.test
                is_type_checking = (
                    isinstance(test, ast.Name)
                    and test.id == "TYPE_CHECKING"
                ) or (
                    isinstance(test, ast.Attribute)
                    and test.attr == "TYPE_CHECKING"
                )
                if not is_type_checking:
                    visit_body(node.body)
                visit_body(node.orelse)
            elif isinstance(node, ast.Try):
                visit_body(node.body)
                for h in node.handlers:
                    visit_body(h.body)
                visit_body(node.orelse)
                visit_body(node.finalbody)

    visit_body(tree.body)
    return out


class ImportGraph:
    """Module-level import edges for one source tree."""

    def __init__(self, modules: Dict[str, str]):
        self.modules = modules
        # module -> list of (ddlpc dep, lineno)
        self.internal: Dict[str, List[Tuple[str, int]]] = {}
        # module -> list of (external root, lineno)
        self.external: Dict[str, List[Tuple[str, int]]] = {}
        for name, path in modules.items():
            with open(path, "r", encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:
                    continue  # the AST rules report syntax errors
            is_pkg = os.path.basename(path) == "__init__.py"
            ints: List[Tuple[str, int]] = []
            exts: List[Tuple[str, int]] = []
            # implicit parent-package edges: importing a.b.c runs a and
            # a.b first
            parent = name.rsplit(".", 1)[0]
            if parent != name:
                ints.append((parent, 0))
            for mod, lineno in _toplevel_imports(tree, name, is_pkg):
                root = mod.split(".")[0]
                if root == "ddlpc_tpu":
                    target = mod
                    while target and target not in modules:
                        target = target.rsplit(".", 1)[0] if "." in target else ""
                    if target and target != name:
                        ints.append((target, lineno))
                else:
                    exts.append((root, lineno))
            self.internal[name] = ints
            self.external[name] = exts

    def reach(
        self, start: str, forbidden
    ) -> Optional[Tuple[List[str], str, int]]:
        """BFS: can ``start`` reach a forbidden external root at import
        time?  Returns (module chain, root, lineno) or None."""
        seen = {start}
        queue: List[Tuple[str, List[str]]] = [(start, [start])]
        while queue:
            mod, path = queue.pop(0)
            for root, lineno in self.external.get(mod, ()):
                if forbidden(root):
                    return path, root, lineno
            for dep, _ in self.internal.get(mod, ()):
                if dep not in seen:
                    seen.add(dep)
                    queue.append((dep, path + [dep]))
        return None


def check_tiers(
    pkg_dir: str, registry: Optional[Dict[str, str]] = None
) -> List[Tuple[str, str, int, str]]:
    """All tier violations for the package at ``pkg_dir``.

    Returns ``(rule_id, path, line, message)`` tuples; empty means every
    declaration is proven.
    """
    registry = MODULE_TIERS if registry is None else registry
    modules = discover_modules(pkg_dir)
    out: List[Tuple[str, str, int, str]] = []
    for name in sorted(set(modules) - set(registry)):
        out.append(
            (
                "tier-undeclared",
                modules[name],
                1,
                f"module {name} is not declared in "
                f"analysis/tiers.py:MODULE_TIERS — new modules must opt "
                f"into a tier explicitly",
            )
        )
    for name in sorted(set(registry) - set(modules)):
        out.append(
            (
                "tier-undeclared",
                os.path.join(pkg_dir, "__init__.py"),
                1,
                f"MODULE_TIERS declares {name} but no such module exists "
                f"— remove the stale entry",
            )
        )
    graph = ImportGraph(modules)

    def forbidden_for(tier: str):
        if tier == JAX:
            return lambda root: False
        if tier == HOST:
            return lambda root: root in JAX_ROOTS
        return lambda root: root not in _STDLIB_NAMES

    for name in sorted(set(modules) & set(registry)):
        tier = registry[name]
        hit = graph.reach(name, forbidden_for(tier))
        if hit is not None:
            chain, root, lineno = hit
            offender = chain[-1]
            out.append(
                (
                    "import-tier",
                    graph.modules[offender],
                    lineno,
                    f"{name} is tier '{tier}' but reaches "
                    f"'import {root}' via {' -> '.join(chain)} "
                    f"(module-level import in {offender})",
                )
            )
        # A declared tier must also bound the declared tiers of direct
        # ddlpc deps — catches a stdlib module leaning on a host module
        # even before the host module grows a forbidden external.
        for dep, lineno in graph.internal.get(name, ()):
            dep_tier = registry.get(dep)
            if dep_tier is not None and _RANK[dep_tier] > _RANK[tier]:
                out.append(
                    (
                        "import-tier",
                        graph.modules[name],
                        lineno or 1,
                        f"{name} (tier '{tier}') imports {dep} "
                        f"(tier '{dep_tier}') at module level — a module "
                        f"may only import its own tier or below",
                    )
                )
    return out
