"""graftlint rule catalogue (G001-G010, G012-G013) and the shared module
analysis. (G014/G015 — the concurrency pack — live in
``tools/graftlint/concurrency.py``; G000/G011 in the lint core.)

Each rule is a class with an ``id``, a one-line ``title``, a docstring
explaining the failure mode it guards, and ``check(tree, path, analysis)``
returning :class:`tools.graftlint.Finding` objects. (G000
lazy-suppression and G011 unused-suppression live in the lint core, not
here — they are properties of the suppression comments, not the code.)
Rules share one :class:`ModuleAnalysis` per file: parent links, the
function table, the in-module call graph, and two derived sets —

- ``traced``: functions handed to a jax tracer (``jit`` / ``lax.scan`` /
  ``grad`` / ``value_and_grad`` / ``vmap`` / ``checkpoint`` / ``defvjp`` /
  ``pallas_call``, as a decorator or a call argument) plus everything they
  reach through in-module calls. Code here runs under tracing: host
  side effects either crash (TracerError) or get baked in silently.
- ``hot``: ``traced`` plus the dispatch loop around it — functions named
  ``fit_batch``/``fit_fused``, functions indexing a ``_jit_train`` cache,
  and their in-module callees. Code here runs per training step on the
  host: a single sync stalls the whole pipelined dispatch queue.

Module-local resolution is deliberately name-based (``self.f(...)`` and
``f(...)`` resolve to any same-named def in the file). In package mode
(the default for ``lint_paths``/the CLI) ``tools/graftlint/symbols.py``
additionally resolves imports, ``module.f``, and method calls on known
classes across every linted file, and rebinds ``traced``/``hot`` to the
cross-module closures; ``analysis.package`` then exposes the package
indexes to rules that need them (G002 cross-module jit sites, G007 mesh
builders, G008 donating factories, G010 worker reachability). Both modes
over-approximate reachability — the cheap, predictable failure mode is a
false positive you silence with an explicit justification, never a silent
false negative from a missed alias.

Adding a rule: subclass ``Rule``, give it the next free id, append to
``RULES``, add a good/bad fixture pair (inline in tests/test_graftlint.py
or files under tests/fixtures/graftlint/), and document it in
docs/STATIC_ANALYSIS.md.
"""

from __future__ import annotations

import ast

from tools.graftlint import Finding

# names that thread model/updater state through a jitted step: a step
# function taking these should donate them (in-place HBM update)
CARRY_PARAM_NAMES = frozenset((
    "params", "params_list", "params_map", "state", "states", "states_list",
    "states_map", "upd", "upd_states", "updater_states", "carry", "carries"))

# jax entry points whose function-valued arguments end up traced
_TRACING_CALLS = frozenset((
    "jit", "scan", "grad", "value_and_grad", "vmap", "pmap", "checkpoint",
    "remat", "custom_vjp", "defvjp", "pallas_call", "while_loop", "cond",
    "fori_loop"))


def name_chain(node):
    """Dotted-name chain of an expression: ``jax.lax.scan`` ->
    ("jax", "lax", "scan"); non-name links (calls, subscripts) truncate."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return tuple(reversed(parts))


def call_chain(call):
    return name_chain(call.func)


class ModuleAnalysis:
    TRACING_CALLS = _TRACING_CALLS

    def __init__(self, tree):
        self.tree = tree
        self.parents = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.functions = [n for n in ast.walk(tree)
                          if isinstance(n, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))]
        self.by_name = {}
        for fn in self.functions:
            self.by_name.setdefault(fn.name, []).append(fn)
        self.calls = {fn: self._called_names(fn) for fn in self.functions}
        self.fn_aliases = self._fn_aliases()
        self.jit_sites = {}   # function node -> jit Call/decorator node
        self.traced_seeds = set(self._traced_seeds())
        self.traced = self._closure(self.traced_seeds)
        self.hot_seeds = self.traced_seeds | set(self._hot_seeds())
        self.hot = self._closure(self.hot_seeds)
        # package mode (tools/graftlint/symbols.py) rebinds traced/hot to
        # the cross-module closures and fills these back-references in
        self.package = None
        self.module_info = None

    # -- construction ---------------------------------------------------
    def own_nodes(self, fn):
        """Nodes belonging to ``fn`` itself: its subtree minus nested
        function/class bodies (those are separate graph vertices)."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def _called_names(self, fn):
        names = set()
        for node in self.own_nodes(fn):
            if isinstance(node, ast.Call):
                chain = call_chain(node)
                if chain:
                    names.add(chain[-1])
        return names

    def _fn_aliases(self):
        """Variable-name -> function-def names for simple function-valued
        bindings: ``step = body``, ``step = body if plan is None else
        tbptt_body``. One hop, names only — enough for the select-a-step-
        builder idiom, where EVERY aliased candidate ends up traced (the
        scan-of-scans dispatch pattern; a miss here silently dropped both
        scan bodies from the traced closure)."""
        aliases = {}

        def cands(expr):
            if isinstance(expr, ast.IfExp):
                return cands(expr.body) + cands(expr.orelse)
            if isinstance(expr, ast.Name) and expr.id in self.by_name:
                return [expr.id]
            return []

        for node in ast.walk(self.tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                names = cands(node.value)
                if names:
                    aliases.setdefault(node.targets[0].id,
                                       set()).update(names)
        return aliases

    def _resolve_fn_arg(self, node):
        """A function-valued argument (``step`` / ``self._loss_fn``) to its
        in-module definitions, if any; follows one simple-alias hop
        (``step_body = body if plan is None else tbptt_body``)."""
        chain = name_chain(node)
        if not chain:
            return []
        direct = self.by_name.get(chain[-1], [])
        if direct:
            return direct
        out = []
        for name in self.fn_aliases.get(chain[-1], ()):
            out.extend(self.by_name.get(name, []))
        return out

    def _traced_seeds(self):
        for fn in self.functions:
            for dec in fn.decorator_list:
                call = dec if isinstance(dec, ast.Call) else None
                target = call.func if call is not None else dec
                tail = (name_chain(target) or ("",))[-1]
                if tail == "partial" and call is not None and call.args:
                    # @functools.partial(jax.jit, donate_argnums=...) — the
                    # idiomatic way to pass jit options to a decorator
                    tail = (name_chain(call.args[0]) or ("",))[-1]
                if tail in _TRACING_CALLS:
                    if tail in ("jit", "pmap"):
                        self.jit_sites.setdefault(fn, dec)
                    yield fn
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            tail = (call_chain(node) or ("",))[-1]
            if tail not in _TRACING_CALLS:
                continue
            for arg in node.args:
                for fn in self._resolve_fn_arg(arg):
                    if tail == "jit":
                        self.jit_sites.setdefault(fn, node)
                    yield fn

    def _hot_seeds(self):
        # the INFERENCE path roots the hot closure exactly like the fit
        # path — a request loop pays for a stray sync the same way a
        # train loop does: output/generate, the serving tier's dispatch
        # loops (serving/ — the batcher and continuous-decode
        # schedulers), and every user of a blessed-signature jit cache
        # (_jit_output/_jit_gen/_jit_decode and their *_signature
        # builders)
        for fn in self.functions:
            if fn.name in ("fit_batch", "fit_fused", "output",
                           "generate", "_batch_loop", "_decode_loop",
                           "_pump_prefill"):
                yield fn
                continue
            for node in self.own_nodes(fn):
                if (isinstance(node, ast.Subscript)
                        and isinstance(node.value, ast.Attribute)
                        and node.value.attr in ("_jit_train",
                                                "_jit_output",
                                                "_jit_gen",
                                                "_jit_decode")):
                    yield fn
                    break
                if (isinstance(node, ast.Call)
                        and (call_chain(node) or ("",))[-1]
                        in ("_output_signature", "_gen_signature",
                            "_decode_signature", "_admit_signature",
                            "_prefill_signature", "_decode_fns",
                            "_prefill_fn")):
                    yield fn
                    break

    def _closure(self, seeds):
        out = set(seeds)
        frontier = list(seeds)
        while frontier:
            fn = frontier.pop()
            for name in self.calls[fn]:
                for callee in self.by_name.get(name, []):
                    if callee not in out:
                        out.add(callee)
                        frontier.append(callee)
        return out

    def enclosing(self, node, kinds):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, kinds):
                return cur
            cur = self.parents.get(cur)
        return None


class Rule:
    id = "G000"
    title = ""

    def check(self, tree, path, analysis):
        raise NotImplementedError

    def finding(self, path, node, message):
        return Finding(self.id, path, node.lineno, node.col_offset + 1,
                       message)


def _is_registry_module(path):
    """The typed knob registry itself. Its env reads and string parses ARE
    the sanctioned implementation (G003 routes everything through it), and
    the interprocedural closure would otherwise mark its helper bodies
    hot/traced through every call site — the rules bite at call sites
    (G003 for raw reads, G004 for trace-time knob reads), never inside the
    registry."""
    return path.replace("\\", "/").endswith("deeplearning4j_tpu/config.py")


def _is_obs_module(path):
    """The observability layer (``deeplearning4j_tpu/obs/``). Its recording
    helpers are called from group-boundary hot code (fit_fused, the guard's
    deferred policy read, the prefetch worker), so the interprocedural hot
    closure pulls their bodies in — where the ``float(v)`` coercions and
    clock reads that ARE the implementation would spray G001/G004 false
    positives at every instrumented seam. The contract that makes the
    carve-out sound (docs/OBSERVABILITY.md): obs records HOST scalars only,
    never takes a device array and never syncs (its one lazy import of jax
    is ``jax.profiler``'s annotation, which touches no device) — a caller
    handing it a device value performs that sync itself, at its own call
    site, where G001 still bites."""
    p = path.replace("\\", "/")
    return "deeplearning4j_tpu/obs/" in p


def _is_env_read(node):
    """The knob name (or "") when ``node`` reads an environment variable:
    os.getenv(k) / bare getenv(k) / os.environ.get(k) / os.environ[k] /
    os.environ.setdefault(k, v) — setdefault returns the value, so it is
    a read with a default, not just a write."""
    if isinstance(node, ast.Call):
        chain = call_chain(node)
        if (chain in (("os", "getenv"), ("getenv",))
                or chain[-2:] in (("environ", "get"),
                                  ("environ", "setdefault"))) and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                return arg.value
            return ""
    if (isinstance(node, ast.Subscript)
            and isinstance(node.ctx, ast.Load)
            and name_chain(node.value)[-1:] == ("environ",)):
        s = node.slice
        if isinstance(s, ast.Constant) and isinstance(s.value, str):
            return s.value
        return ""
    return None


def int_float_shape_exempt(arg):
    """Whether a ``float()``/``int()`` argument is syntactically
    shape-ish (constants, ``.shape``/``.ndim`` reads, ``len()``) — the
    sites G001 deliberately leaves alone. ONE function shared with the
    dataflow layer's G016, whose flow-carried check fires exactly where
    this heuristic exempts: the two rules' boundary must never drift."""
    if isinstance(arg, ast.Constant):
        return True
    for node in ast.walk(arg):
        if isinstance(node, ast.Attribute) and node.attr in ("shape",
                                                            "ndim"):
            return True
        if (isinstance(node, ast.Call)
                and call_chain(node)[-1:] == ("len",)):
            return True
    return False


class HostSyncInHotPath(Rule):
    """G001: a device->host sync on the per-step dispatch path.

    The host loop stays ahead of the accelerator only while every step
    dispatches without waiting on a result. ``.item()``, ``float()`` /
    ``int()`` on a device array, ``np.asarray`` / ``jax.device_get`` /
    ``.block_until_ready()`` all block until the device catches up,
    serializing the pipeline (and, inside a traced function, ``.item()``
    is a TracerError outright). Shape/ndim reads are exempt: they are
    python metadata, not device data."""

    id = "G001"
    title = "host sync inside the hot training path"

    _NP_ROOTS = ("np", "numpy", "onp")

    def _int_float_ok(self, arg):
        return int_float_shape_exempt(arg)

    @staticmethod
    def _scalar_default_params(fn):
        """Parameter names whose declared default is a Python scalar
        constant (``temperature=1.0``, ``top_k=None``, ``seed=0``):
        config-scalar seams of the inference API — a ``float()``/
        ``int()`` parse of one is host argument validation, not a
        device sync. The dataflow layer's G016 still fires when a
        caller's DEVICE value reaches the same parameter through a
        summary, so the boundary stays covered."""
        a = fn.args

        def scalar(d):
            return isinstance(d, ast.Constant) and (
                d.value is None or isinstance(d.value, (bool, int,
                                                        float, str)))

        names = set()
        pos = list(a.posonlyargs or []) + list(a.args)
        for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
            if scalar(d):
                names.add(p.arg)
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if d is not None and scalar(d):
                names.add(p.arg)
        return names

    def check(self, tree, path, analysis):
        if _is_registry_module(path) or _is_obs_module(path):
            return []
        out = []
        for fn in analysis.hot:
            scalar_params = self._scalar_default_params(fn)
            for node in analysis.own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = call_chain(node)
                if not chain:
                    continue
                if chain[-1] in ("item", "block_until_ready") and \
                        isinstance(node.func, ast.Attribute):
                    out.append(self.finding(
                        path, node, f"'.{chain[-1]}()' forces a device sync "
                        f"inside hot function '{fn.name}'"))
                elif chain == ("jax", "device_get") or chain == ("device_get",):
                    out.append(self.finding(
                        path, node, "'jax.device_get' forces a device->host "
                        f"copy inside hot function '{fn.name}'"))
                elif (len(chain) == 2 and chain[0] in self._NP_ROOTS
                        and chain[1] in ("asarray", "array")):
                    out.append(self.finding(
                        path, node, f"'{'.'.join(chain)}' materializes on "
                        f"host inside hot function '{fn.name}'"))
                elif (chain in (("float",), ("int",)) and len(node.args) == 1
                        and not self._int_float_ok(node.args[0])
                        and not (isinstance(node.args[0], ast.Name)
                                 and node.args[0].id in scalar_params)):
                    out.append(self.finding(
                        path, node, f"'{chain[0]}()' on a (possibly device) "
                        f"value syncs inside hot function '{fn.name}'; keep "
                        "scores/metrics device-resident"))
        return out


class RecompileHazard(Rule):
    """G002: patterns that multiply compiled-program signatures or leak
    HBM on the step path.

    (a) ``jax.jit`` built inside a loop: every iteration constructs a new
    callable with an empty cache — one compile per batch, the exact
    regression the fused loop exists to prevent. (b) a jitted train/step
    function that threads model/updater state but does not donate it:
    XLA then allocates fresh buffers and copies every step instead of
    updating in place. (c) container literals inside ``static_argnums`` /
    ``static_argnames`` specs: unhashable statics fail at call time with
    a confusing error."""

    id = "G002"
    title = "jit recompile / non-donated carry hazard"

    _TRAINY = ("step", "train", "fused", "update")
    _DONATE_KWARGS = ("donate_argnums", "donate_argnames")

    def _is_jit_call(self, node):
        chain = call_chain(node)
        return chain[-1:] == ("jit",) and (len(chain) == 1 or
                                           chain[0] in ("jax", "eqx"))

    def check(self, tree, path, analysis):
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if self._is_jit_call(node):
                loop = analysis.enclosing(node, (ast.For, ast.While))
                if loop is not None:
                    out.append(self.finding(
                        path, node, "jax.jit constructed inside a loop: a "
                        "fresh jit has an empty cache, so this compiles "
                        "every iteration — hoist it out of the loop"))
            for kw in node.keywords:
                if kw.arg in ("static_argnums", "static_argnames"):
                    for sub in ast.walk(kw.value):
                        if sub is not kw.value and isinstance(
                                sub, (ast.List, ast.Set, ast.Dict)):
                            out.append(self.finding(
                                path, kw.value, f"container literal inside "
                                f"{kw.arg}: static args must be hashable"))
                            break
        sites = list(analysis.jit_sites.items())
        if analysis.package is not None:
            # jit-wrapping of a step defined in ANOTHER linted file:
            # reported here, at the caller's jit site
            sites.extend((fn, site) for site, fn in
                         analysis.package.cross_jit_sites.get(path, ()))
        for fn, site in sites:
            if not any(t in fn.name.lower() for t in self._TRAINY):
                continue
            args = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
            carried = sorted(args & CARRY_PARAM_NAMES)
            if not carried:
                continue
            kwargs = set()
            if isinstance(site, ast.Call):
                kwargs = {kw.arg for kw in site.keywords}
            if not kwargs & set(self._DONATE_KWARGS):
                out.append(self.finding(
                    path, site, f"jitted step '{fn.name}' threads carry "
                    f"arguments {carried} without donate_argnums: XLA "
                    "allocates+copies instead of updating HBM in place"))
        return out


class UntrackedEnvKnob(Rule):
    """G003: a ``DL4J_TPU_*`` environment read outside the central
    registry.

    Every knob must be declared (name, type, default, doc) in
    ``deeplearning4j_tpu/config.py`` and read through its ``env_flag`` /
    ``env_int`` / ``env_str`` helpers — that is what keeps the generated
    knob table complete, the malformed-value contract uniform, and knob
    reads out of traced code. Writes (monkeypatching in tests/bench) are
    not flagged."""

    id = "G003"
    title = "DL4J_TPU_* env read outside deeplearning4j_tpu/config.py"

    def check(self, tree, path, analysis):
        if _is_registry_module(path):
            return []
        out = []
        for node in ast.walk(tree):
            name = _is_env_read(node)
            if name is not None and name.startswith("DL4J_TPU_"):
                out.append(self.finding(
                    path, node, f"read of {name} bypasses the typed knob "
                    "registry — use deeplearning4j_tpu.config.env_flag/"
                    "env_int/env_float/env_str"))
        return out


class TracedImpurity(Rule):
    """G004: host side effects inside traced (jit/scan) code.

    A traced function runs ONCE per signature; ``time.*``, stdlib/numpy
    ``random``, ``print`` and environment reads execute at trace time and
    their results are baked into the compiled program — the step then
    silently replays stale values forever (use ``jax.random`` /
    ``jax.debug.print`` / pass host state as arguments instead)."""

    id = "G004"
    title = "host impurity inside a traced function"

    def _trace_time_knobs(self, pkg):
        """Knob names the registry declares ``trace_time=True`` — parsed
        from the registry module's AST (graftlint never imports the
        linted code). Returns ``None`` when the registry module is not in
        the linted set (the file-scoped ``--changed`` lane): there the
        declaration cannot be verified, and the fast lane's contract is
        to MISS rather than false-positive — constant ``DL4J_TPU_*``
        names are then presumed declared (the full-scope gate still
        verifies them)."""
        cache = pkg._rule_cache
        if "g004_trace_time" not in cache:
            names = None
            for mi in pkg.modules.values():
                if not _is_registry_module(mi.path):
                    continue
                names = set()
                for node in ast.walk(mi.tree):
                    if not (isinstance(node, ast.Call)
                            and (call_chain(node) or ("",))[-1]
                            == "_declare"):
                        continue
                    if not any(kw.arg == "trace_time"
                               and isinstance(kw.value, ast.Constant)
                               and kw.value.value is True
                               for kw in node.keywords):
                        continue
                    if node.args and isinstance(node.args[0],
                                                ast.Constant):
                        names.add(node.args[0].value)
            cache["g004_trace_time"] = names
        return cache["g004_trace_time"]

    @staticmethod
    def _knob_name_arg(node):
        """The constant knob name of a registry-helper call — positional
        (``env_str("X")``) or keyword (``env_str(name="X")``, the
        helpers' parameter is ``name``); None when computed."""
        arg = node.args[0] if node.args else None
        if arg is None:
            for kw in node.keywords:
                if kw.arg == "name":
                    arg = kw.value
                    break
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value
        return None

    def _registry_read_allowed(self, node, pkg):
        """A registry-helper read in traced code is sanctioned iff the
        knob is DECLARED trace-time (``Knob.trace_time`` in config.py) —
        the declaration replaces the per-site suppression inventory."""
        name = self._knob_name_arg(node)
        if name is None:
            return False   # a computed knob name can't be verified
        if pkg is None:
            return False
        declared = self._trace_time_knobs(pkg)
        if declared is None:
            # registry not in scope (file-scoped lane): presume declared
            # for registry-shaped names; still flag everything else
            return name.startswith("DL4J_TPU_")
        return name in declared

    def _impurity(self, chain):
        if chain in (("print",), ("input",)):
            return f"'{chain[0]}' call"
        if chain[:1] == ("time",) and len(chain) > 1:
            return f"'{'.'.join(chain)}' host-clock read"
        if chain[:1] == ("random",) and len(chain) > 1:
            return f"stdlib '{'.'.join(chain)}'"
        if len(chain) > 2 and chain[0] in ("np", "numpy") \
                and chain[1] == "random":
            return f"'{'.'.join(chain)}' host RNG"
        if chain[-2:] == ("datetime", "now"):
            return f"'{'.'.join(chain)}' host-clock read"
        return None

    _REGISTRY_HELPERS = ("env_flag", "env_int", "env_float", "env_str")

    def check(self, tree, path, analysis):
        if _is_registry_module(path) or _is_obs_module(path):
            return []
        out = []
        for fn in analysis.traced:
            for node in analysis.own_nodes(fn):
                env = _is_env_read(node)
                if env is not None:
                    out.append(self.finding(
                        path, node, f"environment read of "
                        f"{env or 'a variable'} inside traced function "
                        f"'{fn.name}' is baked in at trace time"))
                    continue
                if not isinstance(node, ast.Call):
                    continue
                chain = call_chain(node)
                # the registry helpers are still env reads: routing a knob
                # through config.py does not un-bake it from the trace.
                # A knob the registry DECLARES trace_time=True is the
                # sanctioned exception (the declaration carries the doc
                # caveat — no per-site suppression needed); anything else
                # is a finding.
                if chain[-1:] and chain[-1] in self._REGISTRY_HELPERS:
                    if self._registry_read_allowed(node, analysis.package):
                        continue
                    out.append(self.finding(
                        path, node, f"registry knob read ({chain[-1]}) "
                        f"inside traced function '{fn.name}' is baked in at "
                        "trace time; if trace-time is the documented "
                        "contract, declare the knob trace_time=True in "
                        "deeplearning4j_tpu/config.py"))
                    continue
                what = self._impurity(chain)
                if what is not None:
                    out.append(self.finding(
                        path, node, f"{what} inside traced function "
                        f"'{fn.name}' executes at trace time only"))
        return out


class SwallowAllExcept(Rule):
    """G005: an exception handler that can hide real failures.

    A bare ``except:`` catches ``KeyboardInterrupt``/``SystemExit`` (it
    is flagged unless the body re-raises); ``except Exception: pass``
    silently swallows everything — in the training/parallel paths that
    converts a dead worker or a poisoned collective into a hang or wrong
    numbers. Catch the specific exception, surface an error box, or
    suppress with a justification explaining why best-effort is correct
    here."""

    id = "G005"
    title = "bare except / silent except-Exception-pass"

    _BROAD = ("Exception", "BaseException")

    def check(self, tree, path, analysis):
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            reraises = any(isinstance(n, ast.Raise) for b in node.body
                           for n in ast.walk(b))
            if node.type is None:
                if not reraises:
                    out.append(self.finding(
                        path, node, "bare 'except:' (catches SystemExit/"
                        "KeyboardInterrupt); name the exception"))
                continue
            chain = name_chain(node.type)
            if chain[-1:] and chain[-1] in self._BROAD and \
                    all(isinstance(b, ast.Pass) for b in node.body):
                out.append(self.finding(
                    path, node, f"'except {chain[-1]}: pass' swallows every "
                    "failure silently; narrow it or record the error"))
        return out


def lock_acquire_spans(nodes):
    """Lexical ``<recv>.acquire()`` … ``<recv>.release()`` spans in one
    function's own nodes: ``[(receiver chain, start line, end line,
    receiver expr node)]``. An acquire with no later release on the same
    receiver spans to the end of the function (sys.maxsize stands in) —
    the ``acquire(); try: … finally: release()`` idiom and a genuinely
    leaked lock look the same lexically, and for "is this write guarded"
    the conservative answer (guarded) avoids false positives."""
    acquires, releases = [], []
    for node in nodes:
        if not isinstance(node, ast.Call):
            continue
        chain = call_chain(node)
        if not isinstance(node.func, ast.Attribute) or len(chain) < 2:
            continue
        if chain[-1] == "acquire":
            acquires.append((chain[:-1], node.lineno, node.func.value))
        elif chain[-1] == "release":
            releases.append((chain[:-1], node.lineno))
    spans = []
    for chain, line, recv in acquires:
        end = min((rl for rc, rl in releases
                   if rc == chain and rl >= line), default=10 ** 9)
        spans.append((chain, line, end, recv))
    return spans


class LockDiscipline(Rule):
    """G006: a shared attribute written both inside and outside
    ``with self._lock`` blocks of the same class.

    If some writers take the lock and others do not, the lock protects
    nothing: the unlocked writer races every locked reader (the async
    prefetcher's queue handoff is the canonical at-risk surface).
    Lock scopes are ``with self.<lock>:`` blocks AND explicit
    ``self.<lock>.acquire()`` … ``release()`` spans (the Condition idiom
    and try/finally acquire both count — bare acquire/release pairs used
    to be invisible, silently exempting whole classes from the rule).
    ``__init__``/``__enter__`` construction writes are exempt — no other
    thread can hold a reference yet. The cross-thread, interprocedural
    deepening of this rule is G015 (tools/graftlint/concurrency.py)."""

    id = "G006"
    title = "attribute written both with and without the class lock"

    _EXEMPT_METHODS = ("__init__", "__enter__", "__new__")

    def _lock_names(self, cls):
        names = set()
        acquired, released = set(), set()
        for node in ast.walk(cls):
            if isinstance(node, ast.With):
                for item in node.items:
                    chain = name_chain(item.context_expr)
                    if (len(chain) == 2 and chain[0] == "self"
                            and "lock" in chain[1].lower()):
                        names.add(chain[1])
            elif isinstance(node, ast.Call):
                chain = call_chain(node)
                if len(chain) == 3 and chain[0] == "self":
                    if chain[2] == "acquire":
                        acquired.add(chain[1])
                    elif chain[2] == "release":
                        released.add(chain[1])
        # explicit acquire counts as a lock scope when the name is lockish
        # OR the class also releases it (an acquire/release pair is a lock
        # protocol regardless of the attribute's name — Condition included)
        for attr in acquired:
            if "lock" in attr.lower() or attr in released:
                names.add(attr)
        return names

    def _self_writes(self, node):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                yield t.attr

    def check(self, tree, path, analysis):
        out = []
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            locks = self._lock_names(cls)
            if not locks:
                continue
            locked_writes = {}      # attr -> first locked write node
            unlocked_writes = {}    # attr -> first unlocked write node
            for fn in (n for n in ast.walk(cls)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))):
                if fn.name in self._EXEMPT_METHODS:
                    continue
                spans = [(start, end)
                         for chain, start, end, _recv
                         in lock_acquire_spans(analysis.own_nodes(fn))
                         if len(chain) == 2 and chain[0] == "self"
                         and chain[1] in locks]
                # own_nodes, not ast.walk: a write inside a nested def is
                # that def's own node (this loop visits the nested def as
                # its own fn) — visiting it here too would judge it by the
                # OUTER function's line-based acquire spans, double-
                # recording the one write as both locked and unlocked
                for node in analysis.own_nodes(fn):
                    for attr in self._self_writes(node):
                        if attr in locks or "lock" in attr.lower():
                            continue
                        # walk ALL With ancestors up to the function
                        # boundary (a lock may wrap another context
                        # manager); nested defs don't inherit the caller's
                        # lock — they may run on any thread
                        under = any(start < node.lineno <= end
                                    for start, end in spans)
                        cur = analysis.parents.get(node)
                        while not under and cur is not None and \
                                not isinstance(cur, (ast.FunctionDef,
                                                     ast.AsyncFunctionDef)):
                            if isinstance(cur, ast.With) and any(
                                    name_chain(i.context_expr)[-1:] == (lk,)
                                    for i in cur.items for lk in locks):
                                under = True
                                break
                            cur = analysis.parents.get(cur)
                        (locked_writes if under
                         else unlocked_writes).setdefault(attr, node)
            for attr in sorted(set(locked_writes) & set(unlocked_writes)):
                out.append(self.finding(
                    path, unlocked_writes[attr],
                    f"'{cls.name}.{attr}' is written under "
                    f"{sorted(locks)} elsewhere but without the lock here "
                    "— the lock no longer guarantees exclusion"))
        return out


def spec_ctor_names(mi):
    """Names that construct a ``PartitionSpec`` in one module:
    ``PartitionSpec`` itself plus every import alias (``as P``). The ONE
    vocabulary shared by G007 (constant specs at construction sites) and
    the dataflow layer's G018 (flowed specs at use sites) — the two
    rules must never disagree on what counts as a spec constructor."""
    names = {"PartitionSpec"}
    for alias, (_base, orig) in mi.import_names.items():
        if orig == "PartitionSpec":
            names.add(alias)
    return names


def _const_strings(expr):
    """(strings, fully_constant) inside an expression: every str Constant,
    and whether the expression is built ONLY from tuple/list/constant
    nodes (a non-constant part means the value set is open-ended)."""
    strings = set()
    fully = True
    for node in ast.walk(expr):
        if isinstance(node, ast.Constant):
            if isinstance(node.value, str):
                strings.add(node.value)
        elif not isinstance(node, (ast.Tuple, ast.List, ast.Load)):
            fully = False
    return strings, fully


class ShardingConsistency(Rule):
    """G007: a ``PartitionSpec`` axis name the mesh in scope never defines.

    GSPMD silently treats a spec over an unknown axis as an error at
    ``device_put``/``with_sharding_constraint`` time — or worse, a typo'd
    axis name ("modle") simply fails to shard and the program runs
    replicated, N× slower and N× the memory, with identical numbers. The
    rule collects the axis vocabulary of every mesh the module constructs
    (direct ``Mesh(...)``/``jax.make_mesh`` calls, plus axis-name strings
    passed to or defaulted by *mesh-builder* helpers resolved through the
    package call graph) and checks every constant axis name in a
    ``PartitionSpec``/``P(...)`` against it. Modules that only receive
    their mesh from callers are checked against the package-wide axis
    vocabulary; a module whose own mesh axes are non-constant is skipped
    (its axis set is genuinely open)."""

    id = "G007"
    title = "PartitionSpec axis name not defined by any mesh in scope"

    _MESH_CTORS = ("Mesh", "make_mesh")

    def _axis_arg(self, call):
        """The axis-names argument of a Mesh/make_mesh call."""
        for kw in call.keywords:
            if kw.arg == "axis_names":
                return kw.value
        return call.args[1] if len(call.args) > 1 else None

    def _is_mesh_source(self, fn, pkg, _depth=0):
        """A function that (transitively, ≤2 hops) constructs a Mesh."""
        cache = pkg._rule_cache.setdefault("g007_mesh_source", {})
        if fn in cache:
            return cache[fn]
        cache[fn] = False   # cycle guard
        mi = pkg.fn_module.get(fn)
        if mi is None:
            return False
        result = False
        for node in mi.analysis.own_nodes(fn):
            if isinstance(node, ast.Call) and \
                    (call_chain(node) or ("",))[-1] in self._MESH_CTORS:
                result = True
                break
        if not result and _depth < 2:
            for callee in pkg.xedges.get(fn, ()):
                if self._is_mesh_source(callee, pkg, _depth + 1):
                    result = True
                    break
            if not result:
                for name in mi.analysis.calls.get(fn, ()):
                    for callee in mi.analysis.by_name.get(name, ()):
                        if callee is not fn and self._is_mesh_source(
                                callee, pkg, _depth + 1):
                            result = True
                            break
        cache[fn] = result
        return result

    def _module_vocab(self, path, analysis):
        """(axis vocabulary, has_any_mesh, open) for one module."""
        pkg = analysis.package
        cache = pkg._rule_cache.setdefault("g007_vocab", {})
        if path in cache:
            return cache[path]
        mi = analysis.module_info
        vocab, has_mesh, open_ = set(), False, False
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = call_chain(node)
            if not chain:
                continue
            if chain[-1] in self._MESH_CTORS:
                has_mesh = True
                axis = self._axis_arg(node)
                if axis is None:
                    open_ = True
                    continue
                strings, fully = _const_strings(axis)
                vocab |= strings
                open_ |= not fully
                continue
            # interprocedural: axis names handed to (or defaulted by) a
            # mesh-builder helper count as defined in THIS module
            fn_in = analysis.enclosing(node, (ast.FunctionDef,
                                              ast.AsyncFunctionDef))
            targets = list(mi.analysis.by_name.get(chain[-1], ()))
            if chain[0] != "self" or fn_in is not None:
                targets.extend(pkg.resolve_call(mi, fn_in, chain))
            builders = [t for t in set(targets)
                        if self._is_mesh_source(t, pkg)]
            if not builders:
                continue
            has_mesh = True
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                strings, _ = _const_strings(arg)
                vocab |= strings
            for t in builders:
                a = t.args
                for default in list(a.defaults) + list(a.kw_defaults):
                    if isinstance(default, ast.Constant) and \
                            isinstance(default.value, str):
                        vocab.add(default.value)
                tmi = pkg.fn_module.get(t)
                for sub in tmi.analysis.own_nodes(t):
                    if isinstance(sub, ast.Call) and \
                            (call_chain(sub) or ("",))[-1] in self._MESH_CTORS:
                        axis = self._axis_arg(sub)
                        if axis is not None:
                            strings, _ = _const_strings(axis)
                            vocab |= strings
        cache[path] = (vocab, has_mesh, open_)
        return cache[path]

    def _package_vocab(self, pkg):
        """(union vocabulary, any_open): a single open axis set anywhere
        makes the package union incomplete, so mesh-less modules cannot
        be checked against it."""
        if "g007_pkg_vocab" not in pkg._rule_cache:
            vocab, any_open = set(), False
            for p, mi in pkg.modules.items():
                v, _, open_ = self._module_vocab(p, mi.analysis)
                vocab |= v
                any_open |= open_
            pkg._rule_cache["g007_pkg_vocab"] = (vocab, any_open)
        return pkg._rule_cache["g007_pkg_vocab"]

    def _spec_ctor_names(self, mi):
        return spec_ctor_names(mi)

    def check(self, tree, path, analysis):
        pkg = analysis.package
        mi = analysis.module_info
        if pkg is None or mi is None:
            return []
        vocab, has_mesh, open_ = self._module_vocab(path, analysis)
        if open_:
            return []          # this module's own axis set is unknowable
        if not has_mesh:
            vocab, any_open = self._package_vocab(pkg)
            if any_open:
                return []      # some module's axes are non-constant: the
                               # package union is incomplete, don't guess
        if not vocab:
            return []          # nothing to check against (no meshes at all)
        ctors = self._spec_ctor_names(mi)
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if (call_chain(node) or ("",))[-1] not in ctors:
                continue
            for arg in node.args:
                strings, _ = _const_strings(arg)
                for axis in sorted(strings - vocab):
                    out.append(self.finding(
                        path, node, f"PartitionSpec axis '{axis}' is not "
                        f"defined by any mesh in scope (known axes: "
                        f"{sorted(vocab)}); a misspelt axis silently "
                        "degrades to replication"))
        return out


class UseAfterDonate(Rule):
    """G008: an array read again after being donated to a jitted call.

    ``donate_argnums`` hands the argument's HBM buffer to XLA: after the
    call the old array is *deleted* and any later read raises
    ``RuntimeError: Array has been deleted`` — but only at run time, on
    the accelerator, often many steps in (the fused loop's donated carry
    makes this an easy bug to write). The rule indexes every donating
    callable it can see — jit-decorated defs, ``x = jax.jit(f,
    donate_argnums=...)`` bindings, ``self.attr[...] = jit_factory()``
    caches whose factory returns a donating jit — then flags a donated
    argument that is read again after the call without an intervening
    rebind (the canonical safe shape ``params = step(params, x)``
    rebinds, so it passes). A donating call inside a loop whose donated
    argument is never rebound in that loop is flagged too: iteration 2
    passes an already-deleted array."""

    id = "G008"
    title = "use of an array after donating it to a jitted call"

    def _donation_of_expr(self, expr, mi, pkg, _depth=0):
        """Donated positions/kwarg-names if ``expr`` evaluates to a
        donating jitted callable: a ``jax.jit(..., donate_*)`` call, or a
        call to a factory whose return is one (≤2 hops)."""
        if not isinstance(expr, ast.Call) or _depth > 2:
            return None
        chain = call_chain(expr)
        if not chain:
            return None
        if chain[-1] == "jit":
            pos, names = set(), set()
            for kw in expr.keywords:
                if kw.arg == "donate_argnums":
                    s, _ = _const_ints(kw.value)
                    pos |= s
                elif kw.arg == "donate_argnames":
                    s, _ = _const_strings(kw.value)
                    names |= s
            return (pos, names) if (pos or names) else None
        # factory: f() whose `return jax.jit(step, donate_argnums=...)`
        targets = list(mi.analysis.by_name.get(chain[-1], ()))
        if pkg is not None:
            fn_in = self._fn_of(expr, mi)
            if chain[0] != "self" or fn_in is not None:
                targets.extend(pkg.resolve_call(mi, fn_in, chain))
        for t in set(targets):
            tmi = pkg.fn_module.get(t, mi) if pkg is not None else mi
            for node in tmi.analysis.own_nodes(t):
                if isinstance(node, ast.Return) and node.value is not None:
                    got = self._donation_of_expr(node.value, tmi, pkg,
                                                 _depth + 1)
                    if got:
                        return got
        return None

    def _fn_of(self, node, mi):
        return mi.analysis.enclosing(node, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))

    def _decorated_donation(self, fn):
        """Donated positions of a jit-decorated def (plain or
        functools.partial(jax.jit, donate_argnums=...))."""
        for dec in fn.decorator_list:
            call = dec if isinstance(dec, ast.Call) else None
            if call is None:
                continue
            tail = (name_chain(call.func) or ("",))[-1]
            inner_jit = (tail == "partial" and call.args and
                         (name_chain(call.args[0]) or ("",))[-1] == "jit")
            if tail != "jit" and not inner_jit:
                continue
            pos, names = set(), set()
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    s, _ = _const_ints(kw.value)
                    pos |= s
                elif kw.arg == "donate_argnames":
                    s, _ = _const_strings(kw.value)
                    names |= s
            if pos or names:
                return (pos, names)
        return None

    def _donating_table(self, path, analysis):
        """{callable key -> (positions, kwarg names)}. Keys:
        ("name", fn_name) and ("attr", attr_name) — the latter matches
        ``self.<attr>(...)`` and ``self.<attr>[...](...)`` call sites."""
        pkg = analysis.package
        cache = (pkg._rule_cache.setdefault("g008_tables", {})
                 if pkg is not None else {})
        if path in cache:
            return cache[path]
        mi = analysis.module_info
        table = {}
        for fn in analysis.functions:
            got = self._decorated_donation(fn)
            if got:
                table[("name", fn.name)] = got
        for node in ast.walk(analysis.tree):
            if not isinstance(node, ast.Assign):
                continue
            got = self._donation_of_expr(node.value, mi, pkg) \
                if mi is not None else None
            if not got:
                continue
            for tgt in node.targets:
                base = tgt.value if isinstance(tgt, ast.Subscript) else tgt
                chain = name_chain(base)
                if len(chain) == 1:
                    table[("name", chain[0])] = got
                elif len(chain) == 2 and chain[0] == "self":
                    table[("attr", chain[1])] = got
        cache[path] = table
        return table

    def _call_key(self, call):
        func = call.func
        if isinstance(func, ast.Subscript):
            func = func.value
        chain = name_chain(func)
        if len(chain) == 1:
            return ("name", chain[0])
        if len(chain) == 2 and chain[0] == "self":
            return ("attr", chain[1])
        return None

    def _chain_of_target(self, tgt):
        """Chains killed by one assignment target (tuples recurse)."""
        if isinstance(tgt, (ast.Tuple, ast.List)):
            for el in tgt.elts:
                yield from self._chain_of_target(el)
            return
        if isinstance(tgt, ast.Starred):
            yield from self._chain_of_target(tgt.value)
            return
        chain = name_chain(tgt)
        if chain:
            yield chain

    def check(self, tree, path, analysis):
        table = self._donating_table(path, analysis)
        pkg = analysis.package
        out = []
        for fn in analysis.functions:
            calls = []
            for node in analysis.own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                key = self._call_key(node)
                don = table.get(key) if key is not None else None
                if don is None and pkg is not None and key is not None \
                        and key[0] == "name":
                    # cross-module: from mod import train_step (decorated)
                    for t in pkg.resolve_call(
                            analysis.module_info, fn, (key[1],)):
                        don = self._decorated_donation(t)
                        if don:
                            break
                if don:
                    calls.append((node, don))
            if not calls:
                continue
            # one pass over the function's reads/kills
            reads, kills = [], []
            for node in analysis.own_nodes(fn):
                if isinstance(node, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(node, "ctx", None), ast.Load):
                    chain = name_chain(node)
                    if chain:
                        reads.append((chain, node))
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for tgt in targets:
                        for chain in self._chain_of_target(tgt):
                            kills.append((chain, node))
                if isinstance(node, ast.For):
                    for chain in self._chain_of_target(node.target):
                        kills.append((chain, node))
            for call, (positions, kwnames) in calls:
                donated = []
                for i in sorted(positions):
                    if i < len(call.args):
                        chain = name_chain(call.args[i])
                        if chain:
                            donated.append((chain, call.args[i]))
                for kw in call.keywords:
                    if kw.arg in kwnames:
                        chain = name_chain(kw.value)
                        if chain:
                            donated.append((chain, kw.value))
                in_call = {id(n) for n in ast.walk(call)}
                # `x = donating(x)` rebinds the donated name immediately:
                # the deleted buffer is unreachable afterwards
                owner = analysis.enclosing(call, (ast.Assign,))
                rebound = set()
                if owner is not None and owner.value is not None and \
                        id(call) in {id(n) for n in ast.walk(owner.value)}:
                    for tgt in owner.targets:
                        rebound |= set(self._chain_of_target(tgt))
                loop = analysis.enclosing(call, (ast.For, ast.While))
                for chain, argnode in donated:
                    if chain in rebound:
                        continue
                    later_kills = [k for c, k in kills if c == chain
                                   and k.lineno >= call.lineno]
                    hit = None
                    for rchain, rnode in reads:
                        if rchain != chain or id(rnode) in in_call:
                            continue
                        if rnode.lineno <= call.lineno:
                            continue
                        if any(k.lineno <= rnode.lineno
                               for k in later_kills):
                            continue
                        hit = rnode
                        break
                    if hit is not None:
                        out.append(self.finding(
                            path, hit, f"'{'.'.join(chain)}' is read after "
                            f"being donated to the jitted call on line "
                            f"{call.lineno}: the buffer is deleted — rebind "
                            "the result or copy before donating"))
                        continue
                    if loop is not None:
                        end = getattr(loop, "end_lineno", loop.lineno)
                        loop_kill = any(
                            loop.lineno <= k.lineno <= (end or k.lineno)
                            for c, k in kills if c == chain)
                        if not loop_kill:
                            out.append(self.finding(
                                path, call, f"'{'.'.join(chain)}' is "
                                "donated inside a loop and never rebound "
                                "in it: the next iteration passes an "
                                "already-deleted array"))
        return out


class DtypeDiscipline(Rule):
    """G009: float64 reaching traced code.

    TPUs have no f64 ALUs, and jax runs with x64 *disabled* by default:
    ``np.float64``/``astype("float64")``/``dtype="float64"`` inside a
    traced function does not fail — jax silently truncates to f32 — so
    the code *looks* like it carries double precision while actually
    computing in single, and on backends with x64 enabled it recompiles
    every caller to a different, slower program. Keep traced code f32/
    bf16 and do genuine f64 work (gradient checks, metrics) host-side, or
    suppress with the justification that the surrounding lane enables x64
    on purpose.

    Two layers share this id. The syntactic form above catches f64
    LITERALS inside traced functions. The flow fold (graftlint v7)
    rides the v3 dataflow facts: a value minted f64 anywhere —
    ``np.float64(x)``, ``astype("float64")``, a flowed ``dtype=``
    object, an f64 helper RETURN crossing a module boundary — fires at
    the point it reaches a traced callee, a ``_jit*[...]`` dispatch, or
    a ``jnp``/``lax`` device op, with the mint site in the message.
    Single-file mode has no cross-module summaries, so helper-routed
    f64 is a ``lint_paths``-only catch (the seeded regression in
    tests/test_detlint.py pins that asymmetry)."""

    id = "G009"
    title = "float64 inside traced code (silently truncated with x64 off)"

    _ROOTS = ("np", "numpy", "onp", "jnp")
    _F64_ATTRS = ("float64", "double")
    _F64_STRINGS = ("float64", "f8", "<f8", ">f8", "double")

    def check(self, tree, path, analysis):
        out = []
        for fn in analysis.traced:
            for node in analysis.own_nodes(fn):
                if isinstance(node, ast.Attribute) and \
                        node.attr in self._F64_ATTRS:
                    chain = name_chain(node)
                    if chain and (chain[0] in self._ROOTS
                                  or chain[:2] == ("jax", "numpy")):
                        out.append(self.finding(
                            path, node, f"'{'.'.join(chain)}' inside traced "
                            f"function '{fn.name}': f64 is silently "
                            "truncated to f32 with x64 off (TPU default)"))
                    continue
                if not isinstance(node, ast.Call):
                    continue
                chain = call_chain(node)
                if chain[-1:] == ("astype",):
                    for arg in node.args:
                        if isinstance(arg, ast.Constant) and \
                                arg.value in self._F64_STRINGS:
                            out.append(self.finding(
                                path, node, f"astype({arg.value!r}) inside "
                                f"traced function '{fn.name}': f64 is "
                                "silently truncated with x64 off"))
                for kw in node.keywords:
                    if kw.arg == "dtype" and \
                            isinstance(kw.value, ast.Constant) and \
                            kw.value.value in self._F64_STRINGS:
                        out.append(self.finding(
                            path, kw.value, f"dtype={kw.value.value!r} "
                            f"inside traced function '{fn.name}': f64 is "
                            "silently truncated with x64 off"))
        pkg = analysis.package
        if pkg is not None:
            # the flow-carried half rides the shared v3 dataflow facts;
            # imported lazily so the syntactic rules stay importable on
            # their own (dataflow imports THIS module at top level)
            from tools.graftlint import dataflow
            facts = dataflow.dataflow_facts(pkg)
            lines = {f.line for f in out}
            for ev in facts.events_by_path.get(path, ()):
                if ev.etype != "f64_traced" or ev.node.lineno in lines:
                    continue
                out.append(self.finding(
                    path, ev.node,
                    f"float64 value (minted by {ev.value.f64}) reaches "
                    f"{ev.extra}: f64 is silently truncated to f32 with "
                    "x64 off (TPU default)"))
        return out


class ThreadAffinity(Rule):
    """G010: a jax call reachable from a prefetch-worker thread.

    The async prefetcher's contract (``datasets/async_iterator.py``) is
    that its worker thread groups and enqueues HOST (numpy) batches only —
    every device op is issued from the consumer thread, in program order.
    The rule statically enforces it: any function reachable (through the whole-package call
    graph) from a ``threading.Thread(target=...)`` entry that is either
    named ``_worker`` or defined in a ``*Iterator`` class must not call
    into ``jax.*``/``jnp.*`` or force device placement/sync. Trainer and
    server threads are out of scope — jax itself is thread-safe; the
    contract is specific to data-pipeline workers."""

    id = "G010"
    title = "jax/device call on the prefetch worker thread"

    _DEVICE_TAILS = ("device_put", "device_get", "block_until_ready")

    def check(self, tree, path, analysis):
        pkg = analysis.package
        if pkg is None:
            return []
        out = []
        for fn in analysis.functions:
            if fn not in pkg.worker_reachable:
                continue
            for node in analysis.own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = call_chain(node)
                if not chain:
                    continue
                if chain[0] in ("jax", "jnp") or \
                        chain[-1] in self._DEVICE_TAILS:
                    out.append(self.finding(
                        path, node, f"'{'.'.join(chain)}' runs on the "
                        f"prefetch worker thread (via '{fn.name}'): this "
                        "thread must never touch jax — stage on the "
                        "consumer thread instead (see "
                        "datasets/async_iterator.py)"))
        return out


class UnboundedBlockingCall(Rule):
    """G012: a blocking primitive with no deadline in a threaded/
    distributed module.

    Code under ``parallel/``, ``datasets/``, ``streaming/``, ``ui/`` and
    ``obs/`` blocks on *peers* — worker threads, sockets, queues fed by
    another thread or process — and the unhappy path there is the peer
    DYING, which turns an unbounded wait into a hung process (the exact
    pre-hardening failure modes: the coordinator's ``complete.wait()``,
    the prefetch consumer's ``queue.get()``, the client's
    ``timeout=None`` connect; the UI server's drain thread and storage
    writers block on peers just the same). The rule flags, in modules
    whose path contains one of those directory names:

    - ``.wait()`` with neither a positional timeout nor ``timeout=``
      (``threading.Event``/condition waits);
    - ``.get()`` with no arguments, ``.get(True)``, or ``block=True``
      without a ``timeout=`` (queue reads; dict-style ``.get(key)`` has a
      positional argument and is exempt);
    - ``socket.create_connection`` without a timeout (or with an explicit
      ``timeout=None``);
    - ``.recv``/``.recvfrom``/``.accept`` in a module that never calls
      ``settimeout`` anywhere (a module that sets deadlines somewhere is
      assumed to manage its sockets deliberately).

    Where blocking IS the design — a server handler woken by a stop
    sentinel, a blocking-by-contract API twin — suppress with the
    justification saying who wakes the waiter."""

    id = "G012"
    title = "unbounded blocking call in a threaded/distributed module"

    _SCOPE_DIRS = frozenset(("parallel", "datasets", "streaming", "ui",
                             "obs", "serving"))
    _RECV_TAILS = frozenset(("recv", "recvfrom", "accept"))

    def _in_scope(self, path):
        parts = path.replace("\\", "/").split("/")
        return any(p in self._SCOPE_DIRS for p in parts[:-1])

    @staticmethod
    def _kwargs(node):
        return {kw.arg: kw.value for kw in node.keywords}

    def check(self, tree, path, analysis):
        if not self._in_scope(path):
            return []
        has_settimeout = any(
            isinstance(n, ast.Call)
            and (call_chain(n) or ("",))[-1] == "settimeout"
            for n in ast.walk(tree))
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = call_chain(node)
            if not chain:
                continue
            tail = chain[-1]
            kwargs = self._kwargs(node)
            if tail == "wait" and isinstance(node.func, ast.Attribute) \
                    and not node.args and "timeout" not in kwargs:
                out.append(self.finding(
                    path, node, "'.wait()' with no timeout blocks forever "
                    "if the setter died; pass a deadline and handle expiry"))
            elif tail == "get" and isinstance(node.func, ast.Attribute) \
                    and "timeout" not in kwargs:
                first = node.args[0] if node.args else None
                queue_like = (not node.args and not kwargs) or (
                    isinstance(first, ast.Constant) and first.value is True
                ) or (isinstance(kwargs.get("block"), ast.Constant)
                      and kwargs["block"].value is True)
                if queue_like:
                    out.append(self.finding(
                        path, node, "queue '.get()' with no timeout blocks "
                        "forever if the producer died; use a bounded get "
                        "loop with a liveness check"))
            elif tail == "create_connection":
                timeout = kwargs.get("timeout")
                if (isinstance(timeout, ast.Constant)
                        and timeout.value is None) or (
                        timeout is None and len(node.args) < 2):
                    out.append(self.finding(
                        path, node, "socket.create_connection without a "
                        "timeout hangs on an unreachable peer; pass "
                        "timeout= (and retry with backoff)"))
            elif tail in self._RECV_TAILS and not has_settimeout:
                out.append(self.finding(
                    path, node, f"'.{tail}()' in a module that never calls "
                    "settimeout: a dead peer blocks this read forever"))
        return out


class NonAtomicCheckpointWrite(Rule):
    """G013: a bare file write in a persistence module bypasses the
    atomic checkpoint protocol.

    Checkpoints under ``utils/`` and ``earlystopping/`` are the last line
    of crash recovery, and a write-in-place is the one failure mode that
    can DESTROY state instead of merely losing progress: a crash between
    truncating ``bestModel.zip`` and finishing the new bytes leaves zero
    loadable checkpoints (the exact pre-hardening LocalFileModelSaver /
    NaN-guard bug). Every durable write must route through
    ``utils/atomic_io.py`` (tmp + fsync + rename + CRC manifest). The
    rule flags, in modules whose path contains one of the scope
    directories (the helper module itself is exempt — it is the one place
    allowed to open files for writing):

    - ``open(path, "w"/"wb"/"a"/"x"...)`` — any writing mode;
    - ``zipfile.ZipFile(path, "w"/"a"/"x")`` — archive writes in place;
    - ``np.save``/``np.savez``/``np.savez_compressed`` whose first
      argument is path-like (a string constant, f-string, ``os.path.join``
      call, or concatenation). A plain name is assumed to be an in-memory
      buffer (``BytesIO``) and skipped — serializing INTO a buffer that
      the atomic helper commits is the idiom the rule exists to enforce.

    A deliberate non-checkpoint write (a lock file, a log) gets a
    suppression naming why torn bytes there are harmless."""

    id = "G013"
    title = "non-atomic checkpoint write in a persistence module"

    _SCOPE_DIRS = frozenset(("utils", "earlystopping"))
    _EXEMPT_FILES = frozenset(("atomic_io.py",))
    _NP_WRITERS = frozenset(("save", "savez", "savez_compressed"))
    _WRITE_MODES = frozenset("wax")

    def _in_scope(self, path):
        parts = path.replace("\\", "/").split("/")
        return (any(p in self._SCOPE_DIRS for p in parts[:-1])
                and parts[-1] not in self._EXEMPT_FILES)

    @staticmethod
    def _mode_of(node, pos):
        """The constant mode string at positional index ``pos`` or the
        ``mode=`` keyword, else None (non-constant modes are skipped —
        recall loses to noise on computed modes, which do not occur in
        checkpoint code)."""
        if len(node.args) > pos and isinstance(node.args[pos], ast.Constant):
            v = node.args[pos].value
            return v if isinstance(v, str) else None
        for kw in node.keywords:
            if kw.arg == "mode" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                return kw.value.value
        return None

    @staticmethod
    def _path_like(expr):
        """Whether a np.save* first argument is a filesystem path rather
        than an in-memory buffer: string constants, f-strings, path
        concatenation, and path-builder calls count; bare names are
        assumed buffers."""
        if isinstance(expr, ast.Constant):
            return isinstance(expr.value, str)
        if isinstance(expr, (ast.JoinedStr, ast.BinOp)):
            return True
        if isinstance(expr, ast.Call):
            chain = call_chain(expr)
            return bool(chain) and chain[-1] in ("join", "abspath",
                                                 "fspath", "str")
        return False

    def check(self, tree, path, analysis):
        if not self._in_scope(path):
            return []
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = call_chain(node)
            if not chain:
                continue
            tail = chain[-1]
            if tail == "open" and len(chain) == 1:
                mode = self._mode_of(node, 1)
                if mode is not None and self._WRITE_MODES & set(mode):
                    out.append(self.finding(
                        path, node,
                        f"open(..., {mode!r}) writes a persistence file in "
                        "place: a crash mid-write destroys the previous "
                        "copy — commit through utils/atomic_io "
                        "(tmp + fsync + rename + CRC manifest)"))
            elif tail == "ZipFile":
                mode = self._mode_of(node, 1)
                if mode is not None and self._WRITE_MODES & set(mode):
                    out.append(self.finding(
                        path, node,
                        f"ZipFile(..., {mode!r}) rewrites a checkpoint "
                        "archive in place; build the entries and commit "
                        "via atomic_io.write_zip_atomic"))
            elif tail in self._NP_WRITERS and len(chain) > 1 \
                    and chain[0] in ("np", "numpy"):
                if node.args and self._path_like(node.args[0]):
                    out.append(self.finding(
                        path, node,
                        f"np.{tail} straight to a path tears the previous "
                        "file on a crash; serialize into a buffer and "
                        "commit via utils/atomic_io"))
        return out


def _const_ints(expr):
    """(ints, fully_constant) — integer twin of :func:`_const_strings`."""
    ints = set()
    fully = True
    for node in ast.walk(expr):
        if isinstance(node, ast.Constant):
            if isinstance(node.value, int) and not isinstance(node.value,
                                                              bool):
                ints.add(node.value)
        elif not isinstance(node, (ast.Tuple, ast.List, ast.Load)):
            fully = False
    return ints, fully


RULES = [HostSyncInHotPath(), RecompileHazard(), UntrackedEnvKnob(),
         TracedImpurity(), SwallowAllExcept(), LockDiscipline(),
         ShardingConsistency(), UseAfterDonate(), DtypeDiscipline(),
         ThreadAffinity(), UnboundedBlockingCall(),
         NonAtomicCheckpointWrite()]
