"""The rule catalog (DESIGN.md §15 has the rendered table).

RPR001–RPR005 port tools/ci_guards.py Guards 1–5 one-to-one (same module
scoping, same detection) so the shim keeps identical behaviour.  RPR010+ are
the jit-aware rules: they predicate on the call graph's hot set — every
function statically reachable from a jitted entry point — instead of on
directory layout.

Adding a rule: write a generator over `LintContext` yielding `Finding`s,
wrap it in a `Rule` with an unused RPR0xx id, and append it to ALL_RULES.
A new engine inherits every hot-path rule for free the moment its class
derives from `RoundEngine` — its `step*` methods become seeds automatically
(repro.lint.callgraph.DEFAULT_SEEDS).
"""
from __future__ import annotations

import ast
from typing import Iterable, Iterator, List, Optional, Sequence, Set, Tuple

from repro.lint.analysis import CallInfo, FunctionInfo, LintContext, ModuleInfo
from repro.lint.model import Finding, Rule, Severity

# --------------------------------------------------------------------------
# shared vocabulary (mirrors tools/ci_guards.py so detection is identical)
# --------------------------------------------------------------------------
TILE_UNPACKS = ("unpack_tile_bits", "unpack_tile_mask")
TILE_DENSE_DISPATCH = ("dense_tiles", "dense_tile_mask")
DENSIFY_CALLS = TILE_UNPACKS + TILE_DENSE_DISPATCH
FRONTIER_UNPACKS = ("unpack_frontier_bits", "unpack_frontier_words")
HOST_CALLBACK_CALLS = ("io_callback", "pure_callback", "debug_callback")
HOST_PRINT_RECEIVERS = ("debug",)
KERNEL_FN_SUFFIX = "_kernel"
ORACLE_FN_SUFFIX = "_oracle"

KERNELS_PKG = "repro.kernels"
DYNGRAPH_PKG = "repro.dyngraph"
HOT_PKGS = ("repro.core", "repro.kernels")
ORACLE_MODULE = "repro.kernels.ref"
TILING_MODULE = "repro.core.tiling"
FRONTIER_ALLOWLIST = {
    ("repro.core.tc_mis", "_result"),
    ("repro.core.distributed", "gather_bool"),
}

HOST_SYNC_METHODS = ("item", "tolist", "block_until_ready", "device_get")
IMPURE_STDLIB = ("random", "time", "datetime")
DTYPE64 = ("float64", "int64", "uint64", "f8")
LOOP_GROWING = (
    "concatenate", "append", "hstack", "vstack", "dstack",
    "column_stack", "insert", "resize",
)
DEPRECATED_SYMBOLS = ("tc_mis", "run_phases", "TCMISConfig")
DEPRECATED_SOURCES = ("repro.core", "repro.core.tc_mis")
DEPRECATION_EXEMPT = ("repro.core.tc_mis", "repro.core")
KERNEL_CALL_ALLOWLIST = frozenset(
    TILE_UNPACKS + ("pack_frontier_column",)
)
KERNEL_PY_BUILTINS = frozenset(
    {"range", "len", "min", "max", "abs", "int", "float", "bool",
     "enumerate", "zip", "tuple"}
)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def _in_pkg(module: str, pkg: str) -> bool:
    return module == pkg or module.startswith(pkg + ".")


def _kernel_module(mi: ModuleInfo) -> bool:
    return _in_pkg(mi.name, KERNELS_PKG) and mi.name != ORACLE_MODULE


def _symbol(stack: Tuple[str, ...]) -> str:
    return ".".join(stack) if stack else "<module>"


def _mk(
    mi: ModuleInfo, rule_id: str, severity: str, node, symbol: str, msg: str
) -> Finding:
    return Finding(
        rule=rule_id,
        severity=severity,
        path=mi.rel,
        line=getattr(node, "lineno", 1),
        col=getattr(node, "col_offset", 0) + 1,
        module=mi.name,
        symbol=symbol,
        message=msg,
    )


def _import_target(mi: ModuleInfo, alias: str) -> Optional[str]:
    """Dotted module an alias ultimately refers to (`np` -> `numpy`,
    `lax` -> `jax`, `tiling` -> `repro.core.tiling`-ish)."""
    tgt = mi.imports.get(alias)
    if tgt is None:
        return None
    if tgt[0] == "module":
        return tgt[1]
    return f"{tgt[1]}.{tgt[2]}"


def _is_jax_rooted(mi: ModuleInfo, name: str) -> bool:
    tgt = _import_target(mi, name)
    return tgt is not None and (tgt == "jax" or tgt.startswith("jax."))


def _is_numpy_rooted(mi: ModuleInfo, name: str) -> bool:
    tgt = _import_target(mi, name)
    return tgt is not None and (tgt == "numpy" or tgt.startswith("numpy."))


def _mentions_traced(mi: ModuleInfo, node: ast.AST) -> bool:
    """Heuristic: does the expression visibly involve a jax value (a call or
    attribute rooted at jnp/lax/jax)?  `int(jnp.sum(x))` yes; `int(T // 32)`
    no.  A plain `int(x)` on a traced local is a documented miss."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and _is_jax_rooted(mi, sub.id):
            return True
    return False


def _hot_report_functions(ctx: LintContext) -> Iterator[FunctionInfo]:
    for fi in ctx.graph.hot_functions(ctx):
        if fi.module in ctx.report:
            yield fi


def _stack_is_sanctioned(stack: Tuple[str, ...], *suffixes: str) -> bool:
    return any(fn.endswith(tuple(suffixes)) for fn in stack)


# --------------------------------------------------------------------------
# RPR001 + RPR002 — Guards 1–2: kernel modules keep tiles packed until VMEM
# --------------------------------------------------------------------------
def _check_kernel_tile_unpack(ctx: LintContext) -> Iterator[Finding]:
    for mi in ctx.report_modules():
        if not _kernel_module(mi):
            continue
        for call in mi.calls:
            if call.name in TILE_UNPACKS and not _stack_is_sanctioned(
                call.stack, KERNEL_FN_SUFFIX
            ):
                yield _mk(
                    mi, "RPR001", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    f"{call.name} called outside a *{KERNEL_FN_SUFFIX} body "
                    f"— this materialises (nt, T, T) in HBM and forfeits the "
                    f"8x packed-DMA reduction",
                )


def _check_kernel_densify(ctx: LintContext) -> Iterator[Finding]:
    for mi in ctx.report_modules():
        if not _kernel_module(mi):
            continue
        for call in mi.calls:
            if call.name in TILE_DENSE_DISPATCH:
                yield _mk(
                    mi, "RPR002", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    f"{call.name} in a kernel module — the whole-array "
                    f"oracle dispatches live in kernels/ref.py only",
                )
            elif call.name == "to_storage":
                yield _mk(
                    mi, "RPR002", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    "to_storage() in a kernel module — kernels must consume "
                    "tiles as stored",
                )


# --------------------------------------------------------------------------
# RPR003 — Guard 3: the dyngraph delta path never densifies outside oracles
# --------------------------------------------------------------------------
def _check_dyngraph_densify(ctx: LintContext) -> Iterator[Finding]:
    watched = DENSIFY_CALLS + ("to_storage",)
    for mi in ctx.report_modules():
        if not _in_pkg(mi.name, DYNGRAPH_PKG):
            continue
        for call in mi.calls:
            if call.name in watched and not _stack_is_sanctioned(
                call.stack, ORACLE_FN_SUFFIX
            ):
                yield _mk(
                    mi, "RPR003", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    f"{call.name} outside a *{ORACLE_FN_SUFFIX} body — the "
                    f"delta path edits packed tiles as packed words, never "
                    f"densifies (DESIGN.md §12)",
                )


# --------------------------------------------------------------------------
# RPR004 — Guard 4: frontier words stay packed outside the sanctioned seams
# --------------------------------------------------------------------------
def _frontier_violation(mi: ModuleInfo, call: CallInfo) -> bool:
    if call.name not in FRONTIER_UNPACKS:
        return False
    if mi.name in (TILING_MODULE, ORACLE_MODULE):
        return False
    allowed = {
        fn for (mod, fn) in FRONTIER_ALLOWLIST if mod == mi.name
    }
    return not any(
        fn.endswith((KERNEL_FN_SUFFIX, ORACLE_FN_SUFFIX)) or fn in allowed
        for fn in call.stack
    )


def _check_frontier_unpack(ctx: LintContext) -> Iterator[Finding]:
    for mi in ctx.report_modules():
        if not _in_pkg(mi.name, "repro"):
            continue
        for call in mi.calls:
            if _frontier_violation(mi, call):
                yield _mk(
                    mi, "RPR004", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    f"{call.name} outside a *{KERNEL_FN_SUFFIX}/"
                    f"*{ORACLE_FN_SUFFIX} body or an allowlisted seam — "
                    f"frontier vectors stay packed words on the hot path "
                    f"(DESIGN.md §13)",
                )


# --------------------------------------------------------------------------
# RPR005 — Guard 5: no host callbacks / debug prints in device-hot modules
# --------------------------------------------------------------------------
def _check_host_callbacks(ctx: LintContext) -> Iterator[Finding]:
    for mi in ctx.report_modules():
        if not any(_in_pkg(mi.name, p) for p in HOT_PKGS):
            continue
        for call in mi.calls:
            if call.name in HOST_CALLBACK_CALLS:
                yield _mk(
                    mi, "RPR005", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    f"{call.name}() in a device-hot module — round-loop "
                    f"observability goes through the telemetry buffer "
                    f"(repro.obs.rounds), never host callbacks",
                )
            elif (
                call.name == "print"
                and call.chain is not None
                and len(call.chain) >= 2
                and call.chain[-2] in HOST_PRINT_RECEIVERS
            ):
                yield _mk(
                    mi, "RPR005", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    "debug.print() in a device-hot module — it forces a "
                    "host sync per round inside the while_loop",
                )
        for node in ast.walk(mi.tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in node.names]
                module = getattr(node, "module", "") or ""
                if "host_callback" in module or any(
                    "host_callback" in n for n in names
                ):
                    yield _mk(
                        mi, "RPR005", Severity.ERROR, node, "<module>",
                        "host_callback import in a device-hot module — the "
                        "legacy host round-trip API is banned here",
                    )


# --------------------------------------------------------------------------
# RPR010 — host sync on the jit-reachable hot path
# --------------------------------------------------------------------------
def _check_host_sync(ctx: LintContext) -> Iterator[Finding]:
    for fi in _hot_report_functions(ctx):
        mi = ctx.modules[fi.module]
        for call in fi.calls:
            if call.chain is None:
                continue
            name = call.chain[-1]
            if len(call.chain) >= 2 and name in HOST_SYNC_METHODS:
                yield _mk(
                    mi, "RPR010", Severity.ERROR, call.node, fi.qualname,
                    f".{name}() in jit-reachable `{fi.qualname}` — a "
                    f"device->host sync inside the traced hot path "
                    f"serialises the round loop",
                )
            elif len(call.chain) >= 2 and _is_numpy_rooted(mi, call.chain[0]):
                yield _mk(
                    mi, "RPR010", Severity.ERROR, call.node, fi.qualname,
                    f"numpy call `{'.'.join(call.chain)}` in jit-reachable "
                    f"`{fi.qualname}` — host numpy on traced values forces "
                    f"a transfer (use jnp)",
                )
            elif (
                len(call.chain) == 1
                and name in ("float", "int", "bool")
                and any(
                    _mentions_traced(mi, a) for a in call.node.args
                )
            ):
                yield _mk(
                    mi, "RPR010", Severity.ERROR, call.node, fi.qualname,
                    f"{name}() over a jax expression in jit-reachable "
                    f"`{fi.qualname}` — python scalar conversion is a "
                    f"blocking device->host sync",
                )


# --------------------------------------------------------------------------
# RPR011 — trace impurity on the hot path
# --------------------------------------------------------------------------
def _check_impurity(ctx: LintContext) -> Iterator[Finding]:
    for fi in _hot_report_functions(ctx):
        mi = ctx.modules[fi.module]
        for line in fi.global_decls:
            anchor = type("A", (), {"lineno": line, "col_offset": 0})
            yield _mk(
                mi, "RPR011", Severity.ERROR, anchor, fi.qualname,
                f"global/nonlocal mutation in jit-reachable `{fi.qualname}` "
                f"— traced functions must be pure (the write happens at "
                f"trace time, once, not per call)",
            )
        for call in fi.calls:
            if call.chain is None:
                continue
            dotted = ".".join(call.chain)
            if len(call.chain) >= 2:
                tgt = _import_target(mi, call.chain[0])
                if tgt in IMPURE_STDLIB or (
                    tgt is not None
                    and tgt.split(".")[0] in IMPURE_STDLIB
                ):
                    yield _mk(
                        mi, "RPR011", Severity.ERROR, call.node, fi.qualname,
                        f"`{dotted}` in jit-reachable `{fi.qualname}` — "
                        f"stdlib {tgt.split('.')[0]} is trace-impure (the "
                        f"value freezes at trace time)",
                    )
                elif (
                    _is_numpy_rooted(mi, call.chain[0])
                    and len(call.chain) >= 3
                    and call.chain[1] == "random"
                ):
                    yield _mk(
                        mi, "RPR011", Severity.ERROR, call.node, fi.qualname,
                        f"`{dotted}` in jit-reachable `{fi.qualname}` — "
                        f"numpy RNG is trace-impure; thread a jax.random "
                        f"key instead",
                    )
            elif call.chain == ("print",):
                yield _mk(
                    mi, "RPR011", Severity.ERROR, call.node, fi.qualname,
                    f"print() in jit-reachable `{fi.qualname}` — prints "
                    f"fire at trace time, not per round",
                )


# --------------------------------------------------------------------------
# RPR012 — dtype discipline on the hot path (no implicit 64-bit)
# --------------------------------------------------------------------------
def _dtype64_expr(mi: ModuleInfo, node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name) and node.id in ("float", "int"):
        return node.id
    if isinstance(node, ast.Attribute) and node.attr in DTYPE64:
        return node.attr
    if isinstance(node, ast.Constant) and node.value in DTYPE64:
        return str(node.value)
    return None


def _check_dtype(ctx: LintContext) -> Iterator[Finding]:
    for fi in _hot_report_functions(ctx):
        mi = ctx.modules[fi.module]
        for call in fi.calls:
            hits: List[str] = []
            if call.name == "astype" and call.node.args:
                d = _dtype64_expr(mi, call.node.args[0])
                if d:
                    hits.append(f"astype({d})")
            for kw in call.node.keywords:
                if kw.arg == "dtype":
                    d = _dtype64_expr(mi, kw.value)
                    if d:
                        hits.append(f"dtype={d}")
            for h in hits:
                yield _mk(
                    mi, "RPR012", Severity.ERROR, call.node, fi.qualname,
                    f"{h} in jit-reachable `{fi.qualname}` — python "
                    f"builtins and 64-bit dtypes promote to float64/int64 "
                    f"(x64 is off; be explicit: jnp.float32 / jnp.int32)",
                )


# --------------------------------------------------------------------------
# RPR013 — loop-carry hygiene inside while_loop / scan / fori_loop bodies
# --------------------------------------------------------------------------
def _growing_call(mi: ModuleInfo, call_node: ast.Call) -> Optional[str]:
    from repro.lint.analysis import attr_chain

    chain = attr_chain(call_node.func)
    if not chain or chain[-1] not in LOOP_GROWING:
        return None
    if len(chain) == 1:
        return chain[-1]
    root_tgt = _import_target(mi, chain[0])
    if root_tgt and (
        root_tgt.startswith("jax") or root_tgt.startswith("numpy")
    ):
        return ".".join(chain)
    return None  # `some_list.append(...)` — not an array op


def _check_loop_carry(ctx: LintContext) -> Iterator[Finding]:
    seen: Set[Tuple[str, int]] = set()
    # named loop-body functions, resolved through the call graph (the body
    # may live in another module than the while_loop that names it)
    for key in sorted(ctx.graph.loop_bodies):
        fi = ctx.function(key)
        if fi is None or fi.module not in ctx.report:
            continue
        mi = ctx.modules[fi.module]
        for call in fi.calls:
            name = _growing_call(mi, call.node)
            if name and (mi.name, call.node.lineno) not in seen:
                seen.add((mi.name, call.node.lineno))
                yield _mk(
                    mi, "RPR013", Severity.ERROR, call.node, fi.qualname,
                    f"`{name}` inside the loop body `{fi.qualname}` — "
                    f"shape-growing ops cannot ride a while_loop/scan carry "
                    f"(XLA requires fixed shapes; preallocate + .at[].set)",
                )
    # lambda loop bodies, anchored on the enclosing function
    for mi in ctx.report_modules():
        for fi in mi.functions.values():
            for lam in fi.loop_lambdas:
                for sub in ast.walk(lam):
                    if isinstance(sub, ast.Call):
                        name = _growing_call(mi, sub)
                        if name and (mi.name, sub.lineno) not in seen:
                            seen.add((mi.name, sub.lineno))
                            yield _mk(
                                mi, "RPR013", Severity.ERROR, sub,
                                fi.qualname,
                                f"`{name}` inside a loop-body lambda of "
                                f"`{fi.qualname}` — shape-growing ops cannot "
                                f"ride a while_loop/scan carry",
                            )


# --------------------------------------------------------------------------
# RPR014 — deprecation: no internal callers of the pre-API shims
# --------------------------------------------------------------------------
def _check_deprecation(ctx: LintContext) -> Iterator[Finding]:
    for mi in ctx.report_modules():
        if mi.name in DEPRECATION_EXEMPT or "test" in mi.name.split(".")[-1]:
            continue
        package = (
            mi.name if mi.path.name == "__init__.py"
            else (mi.name.rsplit(".", 1)[0] if "." in mi.name else "")
        )
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.ImportFrom):
                from repro.lint.analysis import _resolve_relative

                src = _resolve_relative(package, node.module, node.level)
                if src in DEPRECATED_SOURCES:
                    for a in node.names:
                        if a.name in DEPRECATED_SYMBOLS:
                            yield _mk(
                                mi, "RPR014", Severity.ERROR, node,
                                "<module>",
                                f"import of deprecated `{a.name}` from "
                                f"{src} — use the repro.api front door "
                                f"(Solver / SolveOptions, DESIGN.md §10)",
                            )
        for call in mi.calls:
            if call.chain is None or call.chain[-1] not in DEPRECATED_SYMBOLS:
                continue
            flagged = False
            if len(call.chain) == 1:
                tgt = mi.imports.get(call.chain[0])
                flagged = (
                    tgt is not None
                    and tgt[0] == "symbol"
                    and tgt[1] in DEPRECATED_SOURCES
                )
            else:
                root_tgt = _import_target(mi, call.chain[0])
                if root_tgt:
                    dotted = ".".join([root_tgt, *call.chain[1:-1]])
                    flagged = dotted in DEPRECATED_SOURCES
            if flagged:
                yield _mk(
                    mi, "RPR014", Severity.ERROR, call.node,
                    _symbol(call.stack),
                    f"call to deprecated `{call.chain[-1]}` — use the "
                    f"repro.api front door (Solver.solve / Solver.profile / "
                    f"SolveOptions)",
                )


# --------------------------------------------------------------------------
# RPR015 — Pallas kernel hygiene: kernel bodies touch refs + jax ops only
# --------------------------------------------------------------------------
def _kernel_family(ctx: LintContext, fi: FunctionInfo) -> List[FunctionInfo]:
    out = [fi]
    for key in fi.nested:
        sub = ctx.function(key)
        if sub is not None:
            out.extend(_kernel_family(ctx, sub))
    return out


def _check_pallas_hygiene(ctx: LintContext) -> Iterator[Finding]:
    for mi in ctx.report_modules():
        if not _in_pkg(mi.name, KERNELS_PKG):
            continue
        for fi in mi.functions.values():
            if not fi.name.endswith(KERNEL_FN_SUFFIX) or fi.parent:
                continue
            family = _kernel_family(ctx, fi)
            nested_names = {f.name for f in family}
            for member in family:
                for call in member.calls:
                    if call.chain is None:
                        continue
                    root = call.chain[0]
                    if len(call.chain) >= 2:
                        tgt = _import_target(mi, root)
                        if tgt is None or tgt.startswith("jax"):
                            continue  # ref/array methods or jax-family ops
                        yield _mk(
                            mi, "RPR015", Severity.ERROR, call.node,
                            member.qualname,
                            f"`{'.'.join(call.chain)}` inside kernel body "
                            f"`{fi.name}` — kernel bodies may only touch "
                            f"refs and jax/pallas ops ({tgt} is not on the "
                            f"kernel allowlist)",
                        )
                    elif (
                        root not in KERNEL_CALL_ALLOWLIST
                        and root not in KERNEL_PY_BUILTINS
                        and root not in nested_names
                    ):
                        yield _mk(
                            mi, "RPR015", Severity.ERROR, call.node,
                            member.qualname,
                            f"`{root}(...)` inside kernel body `{fi.name}` "
                            f"— not on the kernel call allowlist "
                            f"(refs, jax/pallas ops, in-VMEM pack/unpack "
                            f"helpers and nested defs only)",
                        )


# --------------------------------------------------------------------------
# RPR016 — hot-path densify: the call-graph generalisation of Guard 4
# --------------------------------------------------------------------------
def _check_hot_densify(ctx: LintContext) -> Iterator[Finding]:
    watched = FRONTIER_UNPACKS + ("to_storage",)
    for fi in _hot_report_functions(ctx):
        if fi.module in (TILING_MODULE, ORACLE_MODULE):
            continue
        mi = ctx.modules[fi.module]
        for call in fi.calls:
            if call.name not in watched:
                continue
            allowed = {
                fn for (mod, fn) in FRONTIER_ALLOWLIST if mod == fi.module
            }
            if _stack_is_sanctioned(
                call.stack, KERNEL_FN_SUFFIX, ORACLE_FN_SUFFIX
            ) or any(fn in allowed for fn in call.stack):
                continue
            yield _mk(
                mi, "RPR016", Severity.ERROR, call.node, fi.qualname,
                f"{call.name} in jit-reachable `{fi.qualname}` — a densify "
                f"reached from a jitted entry point smuggles a dense "
                f"round-trip into the packed round body, wherever the "
                f"helper lives (DESIGN.md §13/§15)",
            )


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
ALL_RULES: Tuple[Rule, ...] = (
    Rule(
        id="RPR001", name="kernel-tile-unpack", severity=Severity.ERROR,
        summary="tile unpack outside a *_kernel body in a kernel module",
        rationale="packed tiles must stay packed until VMEM; an unpack "
                  "before pallas_call materialises (nt,T,T) in HBM",
        escapes="kernels/ref.py (the oracle); *_kernel bodies",
        check=_check_kernel_tile_unpack,
    ),
    Rule(
        id="RPR002", name="kernel-densify", severity=Severity.ERROR,
        summary="dense_tiles/dense_tile_mask/to_storage in a kernel module",
        rationale="whole-array densify dispatches belong to the oracle path",
        escapes="kernels/ref.py only",
        check=_check_kernel_densify,
    ),
    Rule(
        id="RPR003", name="dyngraph-densify", severity=Severity.ERROR,
        summary="densify on the dyngraph delta path outside *_oracle",
        rationale="delta application edits packed tiles as packed words; a "
                  "densify turns the O(delta) patch into O(tiles)",
        escapes="*_oracle bodies (reference checks)",
        check=_check_dyngraph_densify,
    ),
    Rule(
        id="RPR004", name="frontier-unpack", severity=Severity.ERROR,
        summary="frontier unpack outside kernel/oracle/seam (module-scoped)",
        rationale="frontier vectors ride the round body as packed words; "
                  "one unpack at the epilogue only",
        escapes="core/tiling.py, kernels/ref.py, *_kernel/*_oracle bodies, "
                "tc_mis._result, distributed.gather_bool",
        check=_check_frontier_unpack,
    ),
    Rule(
        id="RPR005", name="host-callback", severity=Severity.ERROR,
        summary="host callbacks / debug prints in device-hot modules",
        rationale="per-round host round-trips serialise the while_loop and "
                  "destroy the timings telemetry exists to measure",
        escapes="none — use the on-device telemetry buffer (obs.rounds)",
        check=_check_host_callbacks,
    ),
    Rule(
        id="RPR010", name="hot-host-sync", severity=Severity.ERROR,
        summary=".item/.tolist/np.*/float(jnp...) in jit-reachable code",
        rationale="a host sync anywhere in the reachable set of a jitted "
                  "entry point blocks dispatch, wherever the helper lives",
        escapes="suppress on the def line for host-stepped drivers "
                "(e.g. the _run_phases_impl profiler twin)",
        check=_check_host_sync,
    ),
    Rule(
        id="RPR011", name="trace-impurity", severity=Severity.ERROR,
        summary="stdlib random/time/datetime, np RNG, print, global writes "
                "in jit-reachable code",
        rationale="impure values freeze at trace time — the compiled "
                  "program replays the traced constant forever",
        escapes="suppress on the def line for host-stepped drivers",
        check=_check_impurity,
    ),
    Rule(
        id="RPR012", name="dtype-discipline", severity=Severity.ERROR,
        summary="astype(float)/dtype=int/float64 on the hot path",
        rationale="python builtins promote to 64-bit; with x64 off the "
                  "result silently differs between host and device",
        escapes="none — spell jnp.float32/jnp.int32 explicitly",
        check=_check_dtype,
    ),
    Rule(
        id="RPR013", name="loop-carry-hygiene", severity=Severity.ERROR,
        summary="shape-growing ops inside while_loop/scan body functions",
        rationale="XLA loop carries are fixed-shape; concatenate/append in "
                  "a body fails at trace or silently retraces",
        escapes="none — preallocate and .at[].set",
        check=_check_loop_carry,
    ),
    Rule(
        id="RPR014", name="deprecated-shim", severity=Severity.ERROR,
        summary="internal import/call of tc_mis/run_phases/TCMISConfig",
        rationale="the repro.api front door owns routing, caching and "
                  "batching; shim callers bypass all three",
        escapes="the shim modules themselves (core/tc_mis.py, "
                "core/__init__.py) and tests",
        check=_check_deprecation,
    ),
    Rule(
        id="RPR015", name="pallas-kernel-hygiene", severity=Severity.ERROR,
        summary="non-allowlisted call inside a Pallas *_kernel body",
        rationale="kernel bodies compile to Mosaic — only refs, jax/pallas "
                  "ops, the in-VMEM pack/unpack helpers and nested defs "
                  "exist there",
        escapes="extend KERNEL_CALL_ALLOWLIST for new in-VMEM helpers",
        check=_check_pallas_hygiene,
    ),
    Rule(
        id="RPR016", name="hot-densify", severity=Severity.ERROR,
        summary="frontier unpack / to_storage anywhere jit-reachable",
        rationale="the call-graph generalisation of Guard 4: a densify "
                  "smuggled in via any module still lands in the round "
                  "body if a jitted entry point reaches it",
        escapes="core/tiling.py + kernels/ref.py (the substrate), "
                "*_kernel/*_oracle bodies, the Guard-4 seams",
        check=_check_hot_densify,
    ),
)

_BY_ID = {r.id: r for r in ALL_RULES}
GUARD_RULE_IDS = ("RPR001", "RPR002", "RPR003", "RPR004", "RPR005")


def get_rules(ids: Optional[Sequence[str]] = None) -> List[Rule]:
    if not ids:
        return list(ALL_RULES)
    unknown = [i for i in ids if i not in _BY_ID]
    if unknown:
        raise KeyError(f"unknown rule id(s): {', '.join(unknown)}")
    return [_BY_ID[i] for i in ids]


def run_rules(
    ctx: LintContext, rules: Optional[Iterable[Rule]] = None
) -> List[Finding]:
    """Run the catalog and apply inline suppressions.  Baseline matching is
    the caller's job (repro.lint.cli) — rules stay baseline-agnostic."""
    import dataclasses

    from repro.lint.model import sort_findings

    out: List[Finding] = []
    for rule in rules if rules is not None else ALL_RULES:
        for f in rule.run(ctx):
            mi = ctx.modules.get(f.module)
            if mi is not None:
                disabled = mi.disabled_rules(f.line)
                if f.rule in disabled or "all" in disabled:
                    f = dataclasses.replace(f, suppressed=True)
            out.append(f)
    return sort_findings(out)
