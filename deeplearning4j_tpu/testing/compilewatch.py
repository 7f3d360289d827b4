"""Runtime compile watcher (the dynamic twin of graftlint G025-G027,
mirroring leakwatch's relationship to G022-G024).

``install()`` subscribes to the process's one ``jax.monitoring``
registration (``obs/compilation.py``, whose callback runs synchronously on
the compiling thread as ``/jax/core/compile/backend_compile_duration``
ends) — the same signal ``tools/compile_counter.py`` counts, generalized
from "how many" to "WHERE FROM": every backend compile records the in-repo
fragment of the triggering call stack. Each event is then *attributed* to
the static dispatch inventory siglint derives
(``tools.graftlint.signatures.signature_inventory_for_paths``): the
innermost recorded frame that falls inside an inventoried dispatch
site's ``(path, lineno..end_lineno)`` range names the (model class,
program family, cache) row that paid the compile. That identity is the
point — a G025 finding and a live stray compile point at the same
``file:line``, statically before the run and dynamically during it.

Three gates ride on the attribution:

- **outlaw compiles** — an event whose innermost in-repo frame sits on
  a line siglint flagged G025 (``outlaw_sites``): the unblessed cache
  the static pass warned about really did compile there. Always a
  violation.
- **steady-state compiles** — any event recorded inside a
  ``with compilewatch.steady():`` region. After warm-up the blessed
  inventory is closed by construction; a compile here is the recompile
  regression class the whole signature discipline exists to prevent.
- **inventory conformance** — ``counts_by_family()`` gives the
  attributed compile count per program family, which the acceptance
  tests compare EXACTLY against the static ladder mirrors
  (``static_kv_ladder`` et al): runtime compiled set == static
  inventory after ``warm_start()`` / the first fit.

Anonymous eager compiles are tolerated by design: ``jnp.zeros`` in
``_init_decode_state`` & friends compile tiny throwaway programs from
lines the dispatch inventory does not cover. They surface in
``events()`` with their frames but attribute to no row, count toward no
family, and trip no gate except ``steady()`` (eager compiles in the
steady loop are exactly as much of a regression as jit ones).

Enablement is the registered ``DL4J_TPU_COMPILEWATCH`` knob (default
OFF — the listener itself is a cheap counter bump, but the stack walk
per compile and the inventory build are test-lane costs; ``bench.py``
opts in explicitly for its steady re-verification). The subscription is
for the life of the process, like the registration under it, and
``uninstall()`` just deactivates recording.

Scope limits (the static side covers what this side cannot):

- compiles triggered before ``install()`` are invisible — the conftest
  installs as early as it can;
- only in-repo frames are recorded (site-packages and a sibling
  checkout are not repo code — separator-anchored prefix, same rule as
  leakwatch), so a compile triggered entirely from third-party code
  attributes to nothing;
- attribution needs the static inventory: when graftlint is not
  importable (an installed wheel without the tools tree) events still
  record, ``attributed()`` is empty, and the gates degrade to
  steady-region checking only.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from contextlib import contextmanager

from deeplearning4j_tpu.obs import compilation

__all__ = ["enabled", "install", "uninstall", "installed", "watch",
           "extend_watch_paths", "inventory", "outlaws", "snapshot",
           "events", "attributed", "counts_by_family", "counts_by_site",
           "steady", "violations", "reset", "report", "assert_clean"]

# RLock for symmetry with leakwatch: the listener can fire on any thread
# (the scheduler thread compiles too) and report() walks state while
# events may still arrive
_state = threading.RLock()
_events: list = []             # [_Event]
_violations: list = []
_serial = [0]
_installed = False
_active = False
_steady_depth = [0]

_MAX_FRAMES = 25

# repo root: the parent of the deeplearning4j_tpu package — only frames
# under it are recorded (same anchoring as leakwatch._site_label)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_watch_paths: list = []        # extra inventory roots (fixture dirs)
_inv_cache = [None]            # (inventory, outlaw set) or None


def enabled():
    """Whether the registered ``DL4J_TPU_COMPILEWATCH`` knob asks for
    the watcher (read at call time; default off)."""
    from deeplearning4j_tpu.config import env_flag
    return env_flag("DL4J_TPU_COMPILEWATCH")


class _Event:
    __slots__ = ("serial", "frames", "steady", "t0")

    def __init__(self, serial, frames, steady):
        self.serial = serial
        self.frames = frames       # [(abspath, lineno)] innermost-first
        self.steady = steady
        self.t0 = time.monotonic()

    def describe(self):
        where = ", ".join(f"{os.path.relpath(p, _REPO_ROOT)}:{ln}"
                          for p, ln in self.frames[:3]) or "<out of repo>"
        tag = " [steady]" if self.steady else ""
        return f"compile #{self.serial} from {where}{tag}"


def _repo_frames():
    """In-repo ``(abspath, lineno)`` frames of the current stack,
    innermost first, capped — the attribution identity."""
    out = []
    f = sys._getframe(2)
    while f is not None and len(out) < _MAX_FRAMES:
        name = f.f_code.co_filename
        if name not in (__file__, compilation.__file__) \
                and not name.startswith("<"):
            ap = os.path.abspath(name)
            if ap.startswith(_REPO_ROOT + os.sep) and \
                    "site-packages" not in ap:
                out.append((ap, f.f_lineno))
        f = f.f_back
    return out


def _on_program(entry):  # noqa: ARG001 — the stack is what is read
    with _state:
        if not _active:
            return
        _serial[0] += 1
        _events.append(_Event(_serial[0], _repo_frames(),
                              _steady_depth[0] > 0))


def installed():
    return _installed


def install():
    """Subscribe (once a process) to the compile listener and start
    recording. Idempotent."""
    global _installed, _active
    with _state:
        compilation.subscribe(_on_program)
        _installed = True
        _active = True


def uninstall():
    """Stop recording. The subscription stays (one per process) but drops
    every event while inactive."""
    global _active
    with _state:
        _active = False


@contextmanager
def watch():
    """``with compilewatch.watch():`` — record for the block; on exit
    deactivate ONLY if this block did the activating (a session-wide
    install, e.g. the chaos lane's conftest, survives nested use)."""
    already = _installed and _active
    install()
    try:
        yield sys.modules[__name__]
    finally:
        if not already:
            uninstall()


# ---- static-inventory attribution -----------------------------------------

def extend_watch_paths(*paths):
    """Add inventory roots beyond the installed package (fixture dirs in
    tests). Invalidates the cached inventory."""
    with _state:
        for p in paths:
            ap = os.path.abspath(p)
            if ap not in _watch_paths:
                _watch_paths.append(ap)
        _inv_cache[0] = None


def _inventory_pair():
    with _state:
        cached = _inv_cache[0]
        if cached is not None:
            return cached
        roots = [os.path.join(_REPO_ROOT, "deeplearning4j_tpu")]
        roots += list(_watch_paths)
        try:
            from tools.graftlint.signatures import (
                signature_inventory_for_paths)
            pair = signature_inventory_for_paths(roots)
        except Exception:
            # no tools tree next to the package (installed wheel):
            # record-only mode, gates degrade to steady checking
            pair = ({}, set())
        _inv_cache[0] = pair
        return pair


def inventory():
    """{(abspath, lineno, end_lineno) -> {family, class, cache}} — the
    static dispatch-site table events attribute to."""
    return dict(_inventory_pair()[0])


def outlaws():
    """{(abspath, lineno)} of every static G025 finding."""
    return set(_inventory_pair()[1])


def _attribute(ev, inv):
    """The innermost recorded frame inside an inventoried dispatch
    site's line range, or None (anonymous eager compile / helper)."""
    for ap, ln in ev.frames:
        for (path, lo, hi), row in inv.items():
            if ap == path and lo <= ln <= hi:
                return (path, lo, hi), row
    return None


# ---- query surfaces --------------------------------------------------------

def snapshot():
    """An opaque marker: pass to the query/gate functions to scope them
    to compiles recorded AFTER this point (the per-test gate's shape)."""
    with _state:
        return _serial[0]


def events(since=0):
    with _state:
        return [e for e in _events if e.serial > since]


def attributed(since=0):
    """[(event, (path, lo, hi), row)] for every event since the marker
    that lands in the static dispatch inventory."""
    inv = _inventory_pair()[0]
    out = []
    for ev in events(since):
        hit = _attribute(ev, inv)
        if hit is not None:
            out.append((ev, hit[0], hit[1]))
    return out


def counts_by_family(since=0):
    """{program family: attributed compile count} — the EXACT-match side
    of the inventory-conformance acceptance tests."""
    out = {}
    for _ev, _site, row in attributed(since):
        out[row["family"]] = out.get(row["family"], 0) + 1
    return out


def counts_by_site(since=0):
    """{(relpath, lineno): attributed compile count} keyed by dispatch
    site — relpath so test expectations are host-independent."""
    out = {}
    for _ev, (path, lo, _hi), _row in attributed(since):
        key = (os.path.relpath(path, _REPO_ROOT), lo)
        out[key] = out.get(key, 0) + 1
    return out


@contextmanager
def steady():
    """Declare a steady-state region: the blessed inventory is closed,
    so ANY compile recorded inside (jit or eager, attributed or not) is
    a violation surfaced by :func:`assert_clean`."""
    with _state:
        _steady_depth[0] += 1
    try:
        yield
    finally:
        with _state:
            _steady_depth[0] -= 1


def violations():
    with _state:
        return list(_violations)


def reset():
    """Drop recorded events and violations (the session gate calls this
    between suites; the inventory cache survives — source does not
    change mid-process)."""
    with _state:
        _events.clear()
        _violations.clear()


def _gate_failures(since):
    inv, outlaw = _inventory_pair()
    bad = []
    for ev in events(since):
        if ev.steady:
            bad.append((ev, "steady-state compile"))
            continue
        innermost = ev.frames[0] if ev.frames else None
        if innermost is not None and innermost in outlaw:
            bad.append((ev, "compile at a G025-flagged unblessed site"))
    return bad


def report(since=0):
    bad = _gate_failures(since)
    if not bad:
        return "compilewatch: no stray compiles"
    out = [f"compilewatch: {len(bad)} stray compile(s)"]
    for ev, why in bad:
        out.append(f"  - {ev.describe()} — {why}")
    out.append("the blessed signature inventory is closed after warm-up: "
               "route new keys through a *_signature builder and warm "
               "them, or bound/evict the cache (docs/STATIC_ANALYSIS.md, "
               "graftlint G025-G027)")
    return "\n".join(out)


def assert_clean(since=0):
    """Raise ``AssertionError`` for every steady-region or outlaw-site
    compile since the marker — and record the violation for the session
    gate, so a swallowed per-test failure still fails the chaos lane."""
    bad = _gate_failures(since)
    if bad:
        msg = report(since)
        with _state:
            for ev, why in bad:
                site = ev.frames[0] if ev.frames else None
                _violations.append({"why": why, "site": site})
        raise AssertionError(msg)
