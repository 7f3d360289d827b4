"""Whole-package symbol table and cross-module call graph (graftlint v2).

PR 2's graftlint resolved calls module-locally: ``self.f(...)`` and
``f(...)`` matched any same-named def *in the file*, so a host sync or an
undonated carry reached through an import — ``models/multi_layer_network.py``
→ ``nn/helpers.py`` → ``ui/stats.py`` — was invisible. This module closes
that gap with a **two-pass** analysis over every linted file:

Pass 1 (per file, cached): parse, build the module's :class:`ModuleInfo` —
its import table (``import a.b as m`` / ``from a.b import f as g``,
relative forms included), its class table (methods + base-class names),
its top-level defs, and the shared per-module :class:`ModuleAnalysis`.

Pass 2 (package-wide): resolve every call site to definitions anywhere in
the linted set and recompute the ``traced``/``hot`` closures over the
combined graph. Resolution, most precise first:

- ``f(...)``           → local def, else the from-imported def (re-exports
                         through ``__init__`` followed one hop)
- ``mod.f(...)``       → def ``f`` in the imported module (``import a.b``,
                         ``import a.b as mod``, and from-imported
                         submodules all resolve)
- ``Cls.m(...)`` /
  ``Cls(...).m``       → method ``m`` of the known class ``Cls``
- ``self.m(...)``      → method ``m`` of the enclosing class or any
                         resolvable base class
- ``self.attr.m(...)`` → method ``m`` of ``Cls`` when the class assigns
                         ``self.attr = Cls(...)``
- ``x.m(...)``         → method ``m`` of ``Cls`` when the function assigns
                         ``x = Cls(...)``; otherwise *every* known class
                         method named ``m`` (recall over precision — the
                         listener/layer dispatch seams are exactly the
                         dynamic calls that hid PR 2's misses), except for
                         ubiquitous container/protocol names
                         (:data:`GENERIC_METHOD_STOPLIST`), which only
                         resolve through a typed receiver.

Known false negatives (documented in docs/STATIC_ANALYSIS.md): the
iteration protocol (``for x in it`` never shows a Call node, so
``__next__`` bodies are only reachable through explicit calls), calls
through containers (``fns[i]()``), and stoplisted method names on untyped
receivers. Everything is matched by *suffix* of the dotted path, so the
same file resolves identically whether linted via a relative or absolute
path.

Like the rest of graftlint this is stdlib-``ast`` only and never imports
the code it lints.
"""

from __future__ import annotations

import ast
import os

from tools.graftlint.rules import ModuleAnalysis, call_chain, name_chain

# method names too generic to resolve through an UNTYPED receiver: they
# overwhelmingly hit dicts/lists/queues/files/locks, and a wrong edge here
# drags half the package into `hot`. A typed receiver (self / known class)
# still resolves them.
GENERIC_METHOD_STOPLIST = frozenset((
    "get", "put", "pop", "append", "extend", "insert", "remove", "clear",
    "items", "keys", "values", "update", "setdefault", "copy", "count",
    "index", "sort", "add", "discard", "union", "join", "split", "strip",
    "lstrip", "rstrip", "format", "replace", "encode", "decode", "lower",
    "upper", "startswith", "endswith", "read", "write", "close", "open",
    "flush", "seek", "readline", "readlines", "start", "run", "wait",
    "set", "is_set", "acquire", "release", "notify", "notify_all",
    "qsize", "get_nowait", "put_nowait", "task_done", "mkdir", "exists",
    "item", "tolist", "astype", "reshape", "ravel", "flatten", "sum",
    "mean", "std", "min", "max", "dot", "transpose", "squeeze", "fill",
    "group", "match", "search", "findall", "send", "recv", "connect",
    "bind", "listen", "accept", "shutdown", "submit", "result", "cancel",
    "register", "next", "is_alive"))


class ClassInfo:
    __slots__ = ("name", "node", "module", "methods", "base_chains",
                 "attr_types")

    def __init__(self, name, node, module):
        self.name = name
        self.node = node
        self.module = module            # ModuleInfo
        self.methods = {}               # name -> FunctionDef (own, not bases)
        self.base_chains = []           # dotted-name tuples of base exprs
        self.attr_types = {}            # self.<attr> -> class-name chain


class ModuleInfo:
    """Pass-1 product for one file: parsed tree + local symbol tables."""

    __slots__ = ("path", "parts", "tree", "analysis", "import_modules",
                 "import_names", "classes", "top_defs", "assigned_classes")

    def __init__(self, path, source, tree=None):
        self.path = path
        self.parts = _module_parts(path)
        # a pre-parsed tree (the incremental cache's content-hash hit)
        # skips the parse; everything derived below is recomputed — only
        # the parse itself is per-file pure
        self.tree = ast.parse(source, filename=path) if tree is None \
            else tree
        self.analysis = ModuleAnalysis(self.tree)
        self.import_modules = {}   # alias -> dotted parts tuple
        self.import_names = {}     # alias -> (module parts, original name)
        self.classes = {}          # name -> ClassInfo
        self.top_defs = {}         # name -> FunctionDef (module top level)
        self._collect_imports()
        self._collect_defs()

    def _collect_imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    parts = tuple(alias.name.split("."))
                    if alias.asname:
                        self.import_modules[alias.asname] = parts
                    else:
                        # `import a.b` binds `a`; attribute chains a.b.f
                        # are matched against the full parts in resolution
                        self.import_modules[parts[0]] = (parts[0],)
                        self.import_modules[alias.name.replace(".", "\0")] = parts
            elif isinstance(node, ast.ImportFrom):
                if node.module is None:
                    base = self.parts[:len(self.parts) - node.level]
                else:
                    base = tuple(node.module.split("."))
                    if node.level:
                        base = self.parts[:len(self.parts) - node.level] + base
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound = alias.asname or alias.name
                    self.import_names[bound] = (base, alias.name)

    def _collect_defs(self):
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.top_defs[node.name] = node
            elif isinstance(node, ast.ClassDef):
                ci = ClassInfo(node.name, node, self)
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        ci.methods[sub.name] = sub
                for base in node.bases:
                    chain = name_chain(base)
                    if chain:
                        ci.base_chains.append(chain)
                # self.<attr> = Cls(...) anywhere in the class body types
                # the attribute for `self.attr.m(...)` resolution
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Assign):
                        continue
                    if not isinstance(sub.value, ast.Call):
                        continue
                    ctor = name_chain(sub.value.func)
                    if not ctor:
                        continue
                    for tgt in sub.targets:
                        tchain = name_chain(tgt)
                        if (len(tchain) == 2 and tchain[0] == "self"):
                            ci.attr_types.setdefault(tchain[1], ctor)
                self.classes[node.name] = ci


def _module_parts(path):
    """Dotted-path components of a file, filesystem-root agnostic:
    ``.../deeplearning4j_tpu/nn/helpers.py`` → ("...", "nn", "helpers").
    ``__init__.py`` maps to its package. Imports are matched by *suffix*
    against these, so absolute and relative lint paths resolve alike."""
    norm = os.path.normpath(path).replace("\\", "/")
    parts = [p for p in norm.split("/") if p not in ("", ".", "..")]
    if parts and parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return tuple(parts)


class PackageAnalysis:
    """Pass 2: cross-module resolution + global traced/hot closures.

    Construction is the whole cost; built ONCE per lint run and shared by
    every rule (the parsed-AST / symbol-table cache the tier-1 gate's
    60-second budget depends on). After construction each module's
    ``ModuleAnalysis.traced`` / ``.hot`` includes functions reachable
    through imports, and ``analysis.package`` points back here so rules
    can use the package-level indexes.
    """

    def __init__(self, sources, cache=None):
        self.modules = {}            # path -> ModuleInfo
        self.errors = []             # "path: syntax error: ..."
        self.by_tail = {}            # last dotted part -> [ModuleInfo]
        self.method_index = {}       # method name -> [(ClassInfo, fn node)]
        self.xedges = {}             # fn node -> set of fn nodes (cross-mod)
        self.fn_module = {}          # fn node -> ModuleInfo
        self.cross_jit_sites = {}    # caller path -> [(jit Call, target fn)]
        self._rule_cache = {}        # scratch space for rule-pack indexes
        for path in sorted(sources):
            tree = cache.get_tree(sources[path]) if cache is not None \
                else None
            try:
                mi = ModuleInfo(path, sources[path], tree=tree)
            except SyntaxError as e:
                self.errors.append(f"{path}: syntax error: {e}")
                continue
            if cache is not None and tree is None:
                cache.put_tree(sources[path], mi.tree)
            self.modules[path] = mi
        for mi in self.modules.values():
            self.by_tail.setdefault(mi.parts[-1] if mi.parts else "",
                                    []).append(mi)
            for ci in mi.classes.values():
                for name, fn in ci.methods.items():
                    self.method_index.setdefault(name, []).append((ci, fn))
            for fn in mi.analysis.functions:
                self.fn_module[fn] = mi
        for mi in self.modules.values():
            self._resolve_module_edges(mi)
        self._close_traced_and_hot()
        self.worker_reachable = self._worker_closure()
        for mi in self.modules.values():
            mi.analysis.package = self
            mi.analysis.module_info = mi

    # ---- module / symbol resolution -----------------------------------

    def resolve_module(self, parts):
        """A dotted module path to its ModuleInfo by longest-suffix match
        (``deeplearning4j_tpu.nn.helpers`` matches
        ``/root/repo/deeplearning4j_tpu/nn/helpers.py``)."""
        if not parts:
            return None
        for mi in self.by_tail.get(parts[-1], ()):
            if mi.parts[-len(parts):] == tuple(parts):
                return mi
        return None

    def resolve_symbol(self, parts, name, depth=0):
        """(def | ClassInfo | ModuleInfo) for ``from <parts> import <name>``,
        following one re-export hop through package ``__init__`` files."""
        mi = self.resolve_module(parts)
        if mi is None:
            return None
        if name in mi.top_defs:
            return mi.top_defs[name]
        if name in mi.classes:
            return mi.classes[name]
        sub = self.resolve_module(tuple(parts) + (name,))
        if sub is not None:
            return sub
        if depth < 2 and name in mi.import_names:
            base, orig = mi.import_names[name]
            return self.resolve_symbol(base, orig, depth + 1)
        return None

    def resolve_class_chain(self, mi, chain):
        """A dotted name used as a class reference → ClassInfo, via local
        defs, from-imports, and module imports."""
        if not chain:
            return None
        head, tail = chain[0], chain[-1]
        if len(chain) == 1:
            if head in mi.classes:
                return mi.classes[head]
            if head in mi.import_names:
                base, orig = mi.import_names[head]
                got = self.resolve_symbol(base, orig)
                return got if isinstance(got, ClassInfo) else None
            return None
        target = self._resolve_module_prefix(mi, chain[:-1])
        if target is not None and tail in target.classes:
            return target.classes[tail]
        return None

    def class_and_ancestors(self, ci, _seen=None):
        seen = _seen if _seen is not None else set()
        if ci is None or id(ci) in seen:
            return []
        seen.add(id(ci))
        out = [ci]
        for chain in ci.base_chains:
            base = self.resolve_class_chain(ci.module, chain)
            out.extend(self.class_and_ancestors(base, seen))
        return out

    def method_on(self, ci, name):
        """Method ``name`` on a class or its resolvable ancestors."""
        for cls in self.class_and_ancestors(ci):
            if name in cls.methods:
                return cls.methods[name]
        return None

    def _resolve_module_prefix(self, mi, chain):
        """A leading dotted chain used as a module reference: import alias
        (``import a.b as m`` → m), plain ``import a.b`` (→ a.b...), or a
        from-imported submodule (``from a import b`` → b)."""
        head = chain[0]
        if head in mi.import_modules:
            parts = mi.import_modules[head]
            # `import a.b` bound both "a" and the full dotted key; prefer
            # the longest registered prefix that matches the chain
            full = mi.import_modules.get("\0".join(chain), None)
            if full is not None:
                return self.resolve_module(full)
            if len(chain) > 1 and parts == (head,):
                return self.resolve_module(tuple(chain))
            return self.resolve_module(tuple(parts) + tuple(chain[1:]))
        if head in mi.import_names:
            base, orig = mi.import_names[head]
            got = self.resolve_symbol(base, orig)
            if isinstance(got, ModuleInfo):
                if len(chain) == 1:
                    return got
                return self.resolve_module(got.parts + tuple(chain[1:]))
        return None

    # ---- call-site resolution -----------------------------------------

    def _enclosing_class(self, mi, fn):
        cur = mi.analysis.parents.get(fn)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return mi.classes.get(cur.name)
            cur = mi.analysis.parents.get(cur)
        return None

    def _local_var_types(self, mi, fn):
        """{var name -> ClassInfo} for ``v = Cls(...)`` assignments inside
        ``fn`` (one function's worth; no flow sensitivity)."""
        out = {}
        for node in mi.analysis.own_nodes(fn):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            ctor = name_chain(node.value.func)
            ci = self.resolve_class_chain(mi, ctor) if ctor else None
            if ci is None:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out.setdefault(tgt.id, ci)
        return out

    @staticmethod
    def _accepts(fn, nargs, nkw):
        """Whether a method can plausibly take ``nargs`` positional plus
        ``nkw`` keyword arguments — the arity filter that keeps the
        untyped-receiver fallback from conflating same-named methods with
        different shapes (a 1-arg host-side ``pre_process(ds)`` is not a
        candidate for a 2-arg traced ``pre_process(x, mask)`` call)."""
        if nargs is None:
            return True
        a = fn.args
        dec_tails = {(name_chain(d) or ("",))[-1] for d in fn.decorator_list}
        implicit = 0 if "staticmethod" in dec_tails else 1
        if a.vararg is not None:
            max_pos = None
        else:
            max_pos = max(0, len(a.args) - implicit)
        min_req = max(0, len(a.args) - implicit - len(a.defaults))
        if max_pos is not None and nargs > max_pos:
            return False
        return nargs + nkw >= min_req or a.kwarg is not None

    def resolve_call(self, mi, fn, chain, var_types=None, nargs=None,
                     nkw=0):
        """Cross-module targets (fn nodes) for one call chain inside
        ``fn``. Module-local same-name matches are NOT repeated here —
        ModuleAnalysis already has them. ``nargs``/``nkw`` (positional /
        keyword argument counts of the call, when known) arity-filter the
        untyped-receiver fallback only; typed resolutions are exact
        enough without it."""
        if not chain:
            return ()
        out = []
        tail = chain[-1]
        if len(chain) == 1:
            if tail in mi.import_names:
                base, orig = mi.import_names[tail]
                got = self.resolve_symbol(base, orig)
                if isinstance(got, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.append(got)
                elif isinstance(got, ClassInfo):
                    ctor = self.method_on(got, "__init__")
                    if ctor is not None:
                        out.append(ctor)
            return out
        head = chain[0]
        if head == "self":
            ci = self._enclosing_class(mi, fn)
            if ci is not None:
                if len(chain) == 2:
                    m = self.method_on(ci, tail)
                    if m is not None:
                        return [m]
                elif len(chain) == 3 and chain[1] in ci.attr_types:
                    attr_ci = self.resolve_class_chain(ci.module,
                                                       ci.attr_types[chain[1]])
                    m = self.method_on(attr_ci, tail)
                    if m is not None:
                        return [m]
            return self._generic_methods(tail, nargs, nkw)
        # Cls.m(...) or v.m(...) with a typed receiver
        if len(chain) == 2:
            ci = self.resolve_class_chain(mi, (head,))
            if ci is not None:
                m = self.method_on(ci, tail)
                return [m] if m is not None else []
            if var_types and head in var_types:
                m = self.method_on(var_types[head], tail)
                return [m] if m is not None else []
        # module-qualified function: mod.f / pkg.mod.f
        target = self._resolve_module_prefix(mi, chain[:-1])
        if target is not None:
            if tail in target.top_defs:
                return [target.top_defs[tail]]
            if tail in target.classes:
                ctor = self.method_on(target.classes[tail], "__init__")
                return [ctor] if ctor is not None else []
            return []
        return self._generic_methods(tail, nargs, nkw)

    def _generic_methods(self, name, nargs=None, nkw=0):
        """Untyped-receiver fallback: every known class method with this
        name (the listener/layer dynamic-dispatch seams), except
        stoplisted container/protocol names, arity-filtered when the call
        shape is known."""
        if name in GENERIC_METHOD_STOPLIST:
            return ()
        return [fn for _, fn in self.method_index.get(name, ())
                if self._accepts(fn, nargs, nkw)]

    def _resolve_module_edges(self, mi):
        for fn in mi.analysis.functions:
            var_types = None
            targets = set()
            for node in mi.analysis.own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                if any(isinstance(a, ast.Starred) for a in node.args) or \
                        any(kw.arg is None for kw in node.keywords):
                    nargs, nkw = None, 0      # *args/**kwargs: no filter
                else:
                    nargs, nkw = len(node.args), len(node.keywords)
                chain = call_chain(node)
                if not chain:
                    continue
                # chained construct-and-call: Cls(...).m(...) — name_chain
                # truncates at the inner Call, so resolve the receiver's
                # constructor explicitly
                if len(chain) == 1 and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Call):
                    ctor = call_chain(node.func.value)
                    ci = self.resolve_class_chain(mi, ctor) if ctor else None
                    m = self.method_on(ci, chain[-1]) if ci else None
                    for tgt in ([m] if m is not None else
                                self._generic_methods(chain[-1], nargs, nkw)):
                        if tgt is not fn:
                            targets.add(tgt)
                    continue
                if len(chain) == 2 and var_types is None:
                    var_types = self._local_var_types(mi, fn)
                for tgt in self.resolve_call(mi, fn, chain, var_types,
                                             nargs, nkw):
                    if tgt is not fn:
                        targets.add(tgt)
            if targets:
                self.xedges[fn] = targets

    # ---- global closures ----------------------------------------------

    def _callees(self, fn):
        mi = self.fn_module.get(fn)
        out = set()
        if mi is not None:
            for name in mi.analysis.calls.get(fn, ()):
                out.update(mi.analysis.by_name.get(name, ()))
        out.update(self.xedges.get(fn, ()))
        out.discard(fn)
        return out

    def _closure(self, seeds):
        out = set(seeds)
        frontier = list(seeds)
        while frontier:
            fn = frontier.pop()
            for callee in self._callees(fn):
                if callee not in out:
                    out.add(callee)
                    frontier.append(callee)
        return out

    def _close_traced_and_hot(self):
        traced_seeds = set()
        hot_seeds = set()
        for mi in self.modules.values():
            a = mi.analysis
            traced_seeds |= a.traced_seeds
            hot_seeds |= a.hot_seeds
            # cross-module tracer arguments: jax.jit(mod.step) where step
            # lives in another linted file
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                tail = (call_chain(node) or ("",))[-1]
                if tail not in a.TRACING_CALLS:
                    continue
                for arg in node.args:
                    chain = name_chain(arg)
                    if not chain or chain[0] == "self":
                        continue
                    for fn in self.resolve_call(mi, None, chain):
                        traced_seeds.add(fn)
                        # report cross-module jit wrapping at the CALLER's
                        # jit site (G002 donation check), not inside the
                        # module that merely defines the step
                        if tail in ("jit", "pmap") and \
                                self.fn_module.get(fn) is not mi:
                            self.cross_jit_sites.setdefault(
                                mi.path, []).append((node, fn))
        hot_seeds |= traced_seeds
        traced = self._closure(traced_seeds)
        hot = self._closure(hot_seeds)
        for mi in self.modules.values():
            a = mi.analysis
            a.traced = {fn for fn in a.functions if fn in traced}
            a.hot = {fn for fn in a.functions if fn in hot}

    # ---- thread-affinity reachability (G010) --------------------------

    def _worker_closure(self):
        """Functions reachable from a prefetch-worker thread entry: a
        function handed to ``threading.Thread(target=...)`` that is either
        named ``_worker`` or defined in a class named ``*Iterator``. These
        run on the thread that must NEVER touch jax (the prefetcher's
        contract: every device op is issued, in program order, from the
        consumer thread). Trainer/server thread entries are deliberately out
        of scope — jax itself is thread-safe; the contract is specific to
        data-pipeline workers."""
        seeds = set()
        for mi in self.modules.values():
            a = mi.analysis
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                if (call_chain(node) or ("",))[-1] != "Thread":
                    continue
                for kw in node.keywords:
                    if kw.arg != "target":
                        continue
                    chain = name_chain(kw.value)
                    if not chain:
                        continue
                    cands = list(a.by_name.get(chain[-1], ()))
                    if len(chain) == 2 and chain[0] == "self":
                        fn_in = a.enclosing(node, (ast.FunctionDef,
                                                   ast.AsyncFunctionDef))
                        ci = self._enclosing_class(mi, fn_in) \
                            if fn_in is not None else None
                        m = self.method_on(ci, chain[-1]) if ci else None
                        if m is not None:
                            cands.append(m)
                    for fn in cands:
                        fmi = self.fn_module.get(fn)
                        if fn.name == "_worker":
                            seeds.add(fn)
                            continue
                        cls = (self._enclosing_class(fmi, fn)
                               if fmi is not None else None)
                        if cls is not None and cls.name.endswith("Iterator"):
                            seeds.add(fn)
        return self._closure(seeds)
