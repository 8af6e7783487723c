"""The repo-specific rule set, distilled from hazards PRs 4-9 actually hit.

Each rule documents the invariant it guards and the PR that motivated it;
`docs/ANALYSIS.md` is the narrative version. Rules are deliberately
high-precision: they key on the syntactic shapes the hazards take in this
codebase rather than trying to be a general-purpose linter, and anything
they cannot prove is left to the parity/property tests that remain the
dynamic backstop.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import FileContext, Project, Rule, dotted_name

__all__ = ["ALL_RULES", "RULES_BY_ID", "default_rules"]


# ---------------------------------------------------------------------------
# import-alias resolution (shared by the wall-clock and RNG rules)
# ---------------------------------------------------------------------------

def _import_aliases(tree: ast.Module) -> Dict[str, str]:
    """Map local names to the dotted origin they were imported as:
    `import numpy as np` -> {"np": "numpy"}, `from time import perf_counter
    as pc` -> {"pc": "time.perf_counter"}. Only module-level imports are
    tracked — that is where this repo imports time/numpy/random."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def _resolve(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """Dotted name of a call target with the leading alias expanded."""
    name = dotted_name(node)
    if name is None:
        return None
    head, _, rest = name.partition(".")
    origin = aliases.get(head)
    if origin is None:
        return name
    return f"{origin}.{rest}" if rest else origin


# ---------------------------------------------------------------------------
# no-wall-clock
# ---------------------------------------------------------------------------

class NoWallClock(Rule):
    """Virtual-clock purity (every PR; the sub-50 ms healing claims).

    All simulated time flows from `Fabric.now`; a single `time.time()` or
    `datetime.now()` on a simulated path makes reports machine-dependent
    and kills byte-identical reproduction. Forbidden throughout engine
    source (`src/repro/`), with an explicit allowlist for the modules whose
    *job* is wall-clock measurement. Benchmarks/examples/tests are exempt
    by scope: timing real walls is what a benchmark driver does.
    """

    id = "no-wall-clock"
    description = ("time.time/perf_counter/monotonic/sleep/datetime.now "
                   "forbidden in engine source (virtual-clock purity)")

    FORBIDDEN = {
        "time.time", "time.time_ns",
        "time.perf_counter", "time.perf_counter_ns",
        "time.monotonic", "time.monotonic_ns",
        "time.process_time", "time.process_time_ns",
        "time.sleep",
        "datetime.datetime.now", "datetime.datetime.utcnow",
        "datetime.datetime.today", "datetime.date.today",
    }
    # suffix matches catch `from datetime import datetime; datetime.now()`
    FORBIDDEN_SUFFIXES = ("datetime.now", "datetime.utcnow", "date.today")

    # Modules whose purpose is wall-clock measurement (never on a simulated
    # path): the real-training step timer, the XLA compile-time probe, and
    # the host spans of the real serving path (which read the clock only
    # while a recorder is attached).
    ALLOWED_FILES = {
        "src/repro/training/train_loop.py",
        "src/repro/launch/dryrun.py",
        "src/repro/obs/spans.py",
    }

    def check_file(self, ctx: FileContext, project: Project):
        if not project.is_src(ctx.rel) or project.is_test(ctx.rel):
            return
        if ctx.rel in self.ALLOWED_FILES:
            return
        aliases = _import_aliases(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _resolve(node.func, aliases)
            if name is None:
                continue
            if name in self.FORBIDDEN or name.endswith(self.FORBIDDEN_SUFFIXES):
                yield (node.lineno, node.col_offset,
                       f"wall-clock call `{name}()` in engine source — "
                       "simulated paths must read the fabric's virtual "
                       "clock (Fabric.now)")


# ---------------------------------------------------------------------------
# no-global-rng
# ---------------------------------------------------------------------------

class NoGlobalRng(Rule):
    """Seeded-randomness discipline (PR 8's vmapped-lane == single-seed
    exactness; every determinism pin in the suite).

    Randomness must flow through an explicitly seeded `np.random.Generator`
    (or `jax.random` key): the numpy/stdlib *global* RNGs are hidden shared
    state that any import can perturb. Seeding a generator from `id()`,
    `hash()` or the wall clock is the same hazard wearing a disguise —
    `id()` changes run to run, `hash(str)` changes with PYTHONHASHSEED.
    Applies to the whole tree: an unseeded benchmark or test is exactly as
    unreproducible as an unseeded engine.
    """

    id = "no-global-rng"
    description = ("module-level np.random.* / bare random.* and "
                   "id()/hash()/wall-clock seeds forbidden; use seeded "
                   "np.random.Generator or jax.random keys")

    NP_ALLOWED = {
        "default_rng", "Generator", "SeedSequence", "BitGenerator",
        "PCG64", "PCG64DXSM", "Philox", "SFC64", "MT19937",
    }
    PY_RANDOM_ALLOWED = {"Random"}  # random.Random(seed) is explicit state
    # constructors whose seed argument must be deterministic
    SEEDED_CTORS = ("default_rng", "SeedSequence", "Random", "RandomState",
                    "PRNGKey", "key", "seed", "fold_in")
    BAD_SEED_CALLS = {"id", "hash", "time.time", "time.time_ns",
                      "time.perf_counter", "time.monotonic", "uuid.uuid4"}

    def check_file(self, ctx: FileContext, project: Project):
        aliases = _import_aliases(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _resolve(node.func, aliases)
            if name is None:
                continue
            yield from self._check_global(node, name)
            yield from self._check_seed_args(node, name, aliases)

    def _check_global(self, node: ast.Call, name: str):
        if name.startswith("numpy.random."):
            tail = name[len("numpy.random."):]
            if "." not in tail and tail not in self.NP_ALLOWED:
                yield (node.lineno, node.col_offset,
                       f"global-state RNG `{name}()` — draw from a seeded "
                       "np.random.default_rng(seed) Generator instead")
        elif name.startswith("random."):
            tail = name[len("random."):]
            if "." not in tail and tail not in self.PY_RANDOM_ALLOWED:
                yield (node.lineno, node.col_offset,
                       f"stdlib global RNG `{name}()` — use a seeded "
                       "random.Random(seed) or np.random.default_rng(seed)")

    def _check_seed_args(self, node: ast.Call, name: str, aliases):
        if not name.endswith(self.SEEDED_CTORS):
            return
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if not isinstance(sub, ast.Call):
                    continue
                sub_name = _resolve(sub.func, aliases)
                if sub_name in self.BAD_SEED_CALLS:
                    yield (sub.lineno, sub.col_offset,
                           f"nondeterministic seed: `{sub_name}()` feeding "
                           f"`{name.rsplit('.', 1)[-1]}(...)` changes run "
                           "to run — derive seeds from the spec/config")


# ---------------------------------------------------------------------------
# fma-hazard
# ---------------------------------------------------------------------------

class FmaHazard(Rule):
    """XLA FMA-contraction defense (PR 8's key numerics discovery).

    Inside a compiled `lax.scan` body (or a jitted kernel), a multiply
    whose result feeds an add/sub gets contracted into a single-rounded
    fma — one ulp off the numpy twin, and `optimization_barrier` does NOT
    stop it. The PR 8 idiom routes every such product through a division
    the compiler cannot fold (`(u*v) / one` with a traced always-1.0
    divisor, or an algebraically equivalent `x / (1/s)` reshuffle): a
    division result feeding an add is not a contraction candidate.

    The rule flags `a*b + c` / `c - a*b` where the product is a *direct*
    operand of the add/sub, inside functions that are scanned/jitted:
    defs passed to `lax.scan`/`lax.map`/`while_loop`/`fori_loop`, defs
    decorated with `jit`, and everything nested inside them. Products
    already wrapped in a division pass untouched; pure-integer products
    (shape/index arithmetic) are skipped.
    """

    id = "fma-hazard"
    description = ("unguarded `a*b + c` inside lax.scan/jit bodies — route "
                   "the product through a division (PR 8 idiom) to block "
                   "fma contraction")

    SCAN_TAILS = ("lax.scan", "lax.map", "lax.while_loop", "lax.fori_loop",
                  "lax.cond", "lax.associative_scan")

    def check_file(self, ctx: FileContext, project: Project):
        if not project.is_src(ctx.rel) or project.is_test(ctx.rel):
            return
        aliases = _import_aliases(ctx.tree)
        compiled: List[ast.AST] = []

        # defs by name per enclosing scope, to resolve `lax.scan(step, ...)`
        for scope in ast.walk(ctx.tree):
            if not isinstance(scope, (ast.Module, ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            local_defs = {n.name: n for n in ast.iter_child_nodes(scope)
                          if isinstance(n, ast.FunctionDef)}
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                name = _resolve(node.func, aliases) or ""
                if not name.endswith(self.SCAN_TAILS):
                    continue
                for arg in node.args:
                    if isinstance(arg, ast.Name) and arg.id in local_defs:
                        compiled.append(local_defs[arg.id])
                    elif isinstance(arg, ast.Lambda):
                        compiled.append(arg)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef) and self._is_jitted(node,
                                                                    aliases):
                compiled.append(node)

        seen: Set[int] = set()
        for body in compiled:
            for expr in ast.walk(body):
                if id(expr) in seen:
                    continue
                seen.add(id(expr))
                if (isinstance(expr, ast.BinOp)
                        and isinstance(expr.op, (ast.Add, ast.Sub))):
                    for side in (expr.left, expr.right):
                        if (isinstance(side, ast.BinOp)
                                and isinstance(side.op, ast.Mult)
                                and not self._integer_product(side)):
                            yield (side.lineno, side.col_offset,
                                   "product feeding an add/sub inside a "
                                   "compiled scan/jit body invites fma "
                                   "contraction — divide the product by a "
                                   "traced 1.0 (see scheduler.py's `one` "
                                   "idiom) or restructure as `x / (1/s)`")

    @staticmethod
    def _is_jitted(node: ast.FunctionDef, aliases) -> bool:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = _resolve(target, aliases) or ""
            if name.endswith((".jit", "functools.partial")) or name == "jit":
                if isinstance(dec, ast.Call) and name.endswith("partial"):
                    inner = dec.args[0] if dec.args else None
                    iname = _resolve(inner, aliases) if inner is not None \
                        else None
                    if not (iname or "").endswith("jit"):
                        continue
                return True
        return False

    @staticmethod
    def _integer_product(node: ast.BinOp) -> bool:
        return all(isinstance(s, ast.Constant) and isinstance(s.value, int)
                   for s in (node.left, node.right))


# ---------------------------------------------------------------------------
# unordered-iteration
# ---------------------------------------------------------------------------

class UnorderedIteration(Rule):
    """Ordering-stable iteration (the byte-identical `ScenarioReport` pins
    across the wave and drain toggles, and the library's golden pins).

    Python `set` iteration order depends on element hashes — for strings,
    on PYTHONHASHSEED — so a set iterated into scheduling or report
    building makes whole runs irreproducible. (`dict` is *not* flagged:
    CPython dict iteration is insertion-ordered and deterministic, which
    the engine exploits deliberately.) The rule flags iteration contexts —
    for/comprehensions and order-materializing calls (`list`, `tuple`,
    `enumerate`, `iter`) — whose iterable is syntactically a set: a set
    literal/comprehension, `set(...)`/`frozenset(...)`, a set-operator
    expression, or a local name only ever assigned such values. Wrapping
    in `sorted(...)` (or reducing with min/max/sum/len/any/all) is the
    fix, and passes automatically because the iterable is then the
    `sorted` call, not the set.
    """

    id = "unordered-iter"
    description = ("iterating a set in engine source — hash order is not "
                   "deterministic; wrap in sorted(...) or use a "
                   "list/dict")

    MATERIALIZERS = {"list", "tuple", "enumerate", "iter"}

    def check_file(self, ctx: FileContext, project: Project):
        if not project.is_src(ctx.rel) or project.is_test(ctx.rel):
            return
        for scope in ast.walk(ctx.tree):
            if not isinstance(scope, (ast.Module, ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            set_locals = self._set_locals(scope)
            for node in ast.iter_child_nodes(scope):
                yield from self._check_scope_body(node, set_locals)

    def _check_scope_body(self, node: ast.AST, set_locals: Set[str]):
        """Walk one scope without descending into nested function scopes
        (they get their own `set_locals`)."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        iterables: List[ast.AST] = []
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iterables.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iterables.extend(g.iter for g in node.generators)
        elif isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            if fname in self.MATERIALIZERS and node.args:
                iterables.append(node.args[0])
        for it in iterables:
            if self._is_set_expr(it, set_locals):
                yield (it.lineno, it.col_offset,
                       "iteration over a set — order follows element "
                       "hashes (PYTHONHASHSEED-dependent for strings); "
                       "wrap in sorted(...) to pin it")
        for child in ast.iter_child_nodes(node):
            yield from self._check_scope_body(child, set_locals)

    def _set_locals(self, scope: ast.AST) -> Set[str]:
        """Local names assigned *only* syntactic-set values in this scope."""
        assigned: Dict[str, List[bool]] = {}
        for node in ast.walk(scope):
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                targets = [node.target]
            else:
                continue
            value = getattr(node, "value", None)
            if value is None:
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    assigned.setdefault(t.id, []).append(
                        self._is_set_expr(value, set()))
        return {name for name, kinds in assigned.items() if all(kinds)}

    def _is_set_expr(self, node: ast.AST, set_locals: Set[str]) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            if fname in ("set", "frozenset"):
                return True
            # s.union(t) / s.intersection(t) / ... on a syntactic set
            if isinstance(node.func, ast.Attribute) and node.func.attr in (
                    "union", "intersection", "difference",
                    "symmetric_difference", "copy"):
                return self._is_set_expr(node.func.value, set_locals)
            return False
        if isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.BitAnd, ast.BitOr, ast.BitXor, ast.Sub)):
            return (self._is_set_expr(node.left, set_locals)
                    or self._is_set_expr(node.right, set_locals))
        if isinstance(node, ast.Name):
            return node.id in set_locals
        return False


# ---------------------------------------------------------------------------
# hot-path-alloc
# ---------------------------------------------------------------------------

class HotPathAlloc(Rule):
    """The PR 5 allocation discipline as a decorator-driven contract.

    Functions tagged `@hot_path` (repro.analysis.hotpath) run once per
    slice/completion/tick; PR 4-5 earned their 3-6x by removing per-item
    closures, `functools.partial` wrappers, and comprehension churn from
    exactly these bodies. The rule keeps them out: inside a tagged
    function it flags lambdas/nested defs and comprehensions *inside
    loops* (per-iteration allocation), and any `functools.partial` call
    (the per-op closure PR 5 removed from the fabric heap). One-time setup
    allocations before the loop are fine and not flagged.
    """

    id = "hot-path-alloc"
    description = ("per-iteration closures/comprehensions or "
                   "functools.partial inside an @hot_path body")

    def check_file(self, ctx: FileContext, project: Project):
        aliases = _import_aliases(ctx.tree)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and self._tagged(node):
                yield from self._check_body(node, aliases, loop_depth=0,
                                            root=True)

    @staticmethod
    def _tagged(node: ast.AST) -> bool:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = dotted_name(target) or ""
            if name == "hot_path" or name.endswith(".hot_path"):
                return True
        return False

    def _check_body(self, node: ast.AST, aliases, loop_depth: int,
                    root: bool = False):
        in_loop = loop_depth > 0
        if not root:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                if in_loop:
                    kind = "lambda" if isinstance(node, ast.Lambda) \
                        else f"nested def `{node.name}`"
                    yield (node.lineno, node.col_offset,
                           f"{kind} created inside a loop on a @hot_path "
                           "body — one closure per iteration; hoist it or "
                           "use a shared tagged callback (PR 5 idiom)")
                return  # nested scopes are their own (untagged) world
            if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                 ast.GeneratorExp)) and in_loop:
                yield (node.lineno, node.col_offset,
                       "comprehension inside a loop on a @hot_path body — "
                       "per-iteration list churn; hoist or write into a "
                       "preallocated buffer")
                return
            if isinstance(node, ast.Call):
                name = _resolve(node.func, aliases) or ""
                if name == "partial" or name.endswith("functools.partial"):
                    yield (node.lineno, node.col_offset,
                           "functools.partial on a @hot_path body — "
                           "allocates a wrapper per call; use a shared "
                           "tagged callback instead")
        next_depth = loop_depth + (1 if isinstance(
            node, (ast.For, ast.AsyncFor, ast.While)) else 0)
        for child in ast.iter_child_nodes(node):
            yield from self._check_body(child, aliases, next_depth)


# ---------------------------------------------------------------------------
# twin-drift
# ---------------------------------------------------------------------------

class TwinDrift(Rule):
    """Kernel-twin discipline (the bit-parity contract behind every
    `*_jnp` kernel since PR 4).

    Every public module-level `*_jnp` kernel in engine source must have a
    registered numpy twin and a parity test referencing both, or the
    jax/numpy pair silently drifts apart the first time one side changes.
    Registration is the defining module's `__numpy_twins__` dict:

        __numpy_twins__ = {
            "tent_choose_wave_jnp": "tent_choose_wave",        # same module
            "x_jnp": "SomeClass.method",                        # method twin
            "y_jnp": ["target", "why the signatures differ"],  # waiver
        }

    Unregistered kernels default to the strip-`_jnp` convention. The rule
    checks (1) the twin def exists somewhere in the scanned engine source,
    (2) parameter names match exactly (ignoring a leading `self`) unless
    the registry entry carries a signature waiver string, and (3) at least
    one test file mentions both the kernel and its twin's terminal name.
    """

    id = "twin-drift"
    description = ("*_jnp kernel without a registered numpy twin, with a "
                   "drifted signature, or without a parity test "
                   "referencing both")

    def finalize(self, project: Project):
        defs = self._collect_defs(project)
        test_texts = [ctx.text for ctx in project.contexts
                      if project.is_test(ctx.rel)]
        for ctx in project.contexts:
            if not project.is_src(ctx.rel) or project.is_test(ctx.rel):
                continue
            registry = self._registry(ctx.tree)
            for node in ast.iter_child_nodes(ctx.tree):
                if not isinstance(node, ast.FunctionDef):
                    continue
                if not node.name.endswith("_jnp") or \
                        node.name.startswith("_"):
                    continue
                if ctx.is_suppressed(self.id, node.lineno):
                    # still emitted (suppression is handled downstream);
                    # no extra work needed here
                    pass
                yield from self._check_kernel(
                    ctx, node, registry, defs, test_texts)

    def _check_kernel(self, ctx: FileContext, node: ast.FunctionDef,
                      registry: Dict[str, object], defs, test_texts):
        entry = registry.get(node.name, node.name[:-len("_jnp")])
        waiver = None
        if isinstance(entry, (list, tuple)):
            target, waiver = entry[0], (entry[1] if len(entry) > 1 else "")
        else:
            target = entry
        twin = defs.get(target)
        if twin is None:
            yield (ctx.rel, node.lineno, node.col_offset,
                   f"`{node.name}` has no numpy twin: no def `{target}` in "
                   "engine source — add the twin or register the real one "
                   "in __numpy_twins__")
            return
        twin_node, twin_rel = twin
        if waiver is None:
            jnp_params = self._params(node)
            twin_params = self._params(twin_node, drop_self=True)
            if jnp_params != twin_params:
                yield (ctx.rel, node.lineno, node.col_offset,
                       f"`{node.name}` signature drifted from twin "
                       f"`{target}` ({twin_rel}): {jnp_params} != "
                       f"{twin_params} — fix the drift or register a "
                       "signature waiver in __numpy_twins__")
        terminal = target.rsplit(".", 1)[-1]
        if not any(node.name in text and terminal in text
                   for text in test_texts):
            yield (ctx.rel, node.lineno, node.col_offset,
                   f"no parity test references both `{node.name}` and its "
                   f"twin `{terminal}` — add one to the test tier")

    @staticmethod
    def _params(node: ast.FunctionDef, drop_self: bool = False) -> Tuple:
        args = node.args
        names = [a.arg for a in args.posonlyargs + args.args
                 + args.kwonlyargs]
        if drop_self and names and names[0] in ("self", "cls"):
            names = names[1:]
        return tuple(names)

    @staticmethod
    def _registry(tree: ast.Module) -> Dict[str, object]:
        for node in ast.iter_child_nodes(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__numpy_twins__":
                        try:
                            val = ast.literal_eval(node.value)
                        except ValueError:
                            return {}
                        return val if isinstance(val, dict) else {}
        return {}

    @staticmethod
    def _collect_defs(project: Project):
        """`name` / `Class.method` -> (def node, rel path) over engine
        source. First definition wins; collisions are fine because the rule
        only checks existence + parameter names."""
        out: Dict[str, Tuple[ast.FunctionDef, str]] = {}
        for ctx in project.contexts:
            if not project.is_src(ctx.rel) or project.is_test(ctx.rel):
                continue
            for node in ast.iter_child_nodes(ctx.tree):
                if isinstance(node, ast.FunctionDef):
                    out.setdefault(node.name, (node, ctx.rel))
                elif isinstance(node, ast.ClassDef):
                    for sub in ast.iter_child_nodes(node):
                        if isinstance(sub, ast.FunctionDef):
                            out.setdefault(
                                f"{node.name}.{sub.name}", (sub, ctx.rel))
        return out


ALL_RULES: Sequence[Rule] = (
    NoWallClock(),
    NoGlobalRng(),
    FmaHazard(),
    UnorderedIteration(),
    HotPathAlloc(),
    TwinDrift(),
)

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in ALL_RULES}


def default_rules(only: Optional[Iterable[str]] = None) -> List[Rule]:
    if only is None:
        return list(ALL_RULES)
    unknown = set(only) - set(RULES_BY_ID)
    if unknown:
        raise ValueError(
            f"unknown rule id(s) {sorted(unknown)}; "
            f"have {sorted(RULES_BY_ID)}")
    return [RULES_BY_ID[r] for r in only]
