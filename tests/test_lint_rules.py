"""fluidlint self-tests: one positive + one negative fixture per rule.

A rule regression (pattern stops matching, scope widens/narrows, a rename
breaks registration) fails here loudly instead of silently opening a hole
in the tier-1 gate.  Module rules run through ``analyze_source`` against
in-memory fixtures; the project rule (FL-WIRE-COMPLETE) runs through
``analyze`` against a synthetic repo tree; the baseline machinery gets its
own match/stale/invalid coverage.
"""

import json
import textwrap

import pytest

from tools.fluidlint import (Finding, analyze, analyze_source,
                             apply_baseline, baseline_function_hygiene,
                             load_baseline)

OPS = "fluidframework_tpu/ops/x.py"          # replay + kernel scope
LOADER = "fluidframework_tpu/loader/x.py"    # replay scope only
RUNTIME = "fluidframework_tpu/runtime/x.py"  # event scope only
SERVICE = "fluidframework_tpu/service/x.py"  # replay + serving scope
TESTING = "fluidframework_tpu/testing/x.py"  # exempt everywhere


def findings_for(src, relpath, rule=None):
    out = analyze_source(textwrap.dedent(src), relpath)
    return [f for f in out if rule is None or f.rule == rule]


# -- one (positive, negative) pair per module rule ---------------------------

MODULE_RULE_FIXTURES = {
    "FL-DET-CLOCK": (
        """
        import time
        def hold():
            return time.time() + 5
        """,
        """
        import time
        def hold(clock=time.monotonic):
            return clock() + 5
        """,
        LOADER,
    ),
    "FL-DET-RANDOM": (
        """
        import random
        def jitter():
            return random.random()
        """,
        """
        import random
        def jitter(rng: random.Random):
            return rng.random()
        """,
        LOADER,
    ),
    "FL-DET-SETITER": (
        """
        def order(ids):
            seen = {i for i in ids}
            return [x for x in seen]
        """,
        """
        def order(ids):
            seen = {i for i in ids}
            return [x for x in sorted(seen)]
        """,
        LOADER,
    ),
    "FL-TRACE-HOSTSYNC": (
        """
        import jax
        @jax.jit
        def fold(x):
            return x + x.sum().item()
        """,
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def fold(x):
            return x + jnp.sum(x)
        """,
        OPS,
    ),
    "FL-TRACE-PYCOND": (
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def clamp(x):
            if jnp.sum(x) > 0:
                return x
            return -x
        """,
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def clamp(x):
            return jnp.where(jnp.sum(x) > 0, x, -x)
        """,
        OPS,
    ),
    "FL-TRACE-LOOPJNP": (
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def fold(xs, n):
            acc = xs[0]
            for i in range(n):
                acc = jnp.maximum(acc, xs[i])
            return acc
        """,
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def fold(xs):
            acc = xs[0]
            for i in range(4):  # bounded constant unroll is idiomatic
                acc = jnp.maximum(acc, xs[i])
            return acc
        """,
        OPS,
    ),
    "FL-TRACE-STATIC": (
        """
        import jax
        @jax.jit(static_argnames=("cfg",))
        def fold(x, cfg: dict):
            return x
        """,
        """
        import jax
        @jax.jit(static_argnames=("cfg",))
        def fold(x, cfg: tuple):
            return x
        """,
        OPS,
    ),
    "FL-TRACE-DONATE": (
        """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def extend(buf, rows):
            return buf + rows

        def caller(buf, rows):
            out = extend(buf, rows)
            return out, buf.sum()
        """,
        """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def extend(buf, rows):
            return buf + rows

        def caller(buf, rows):
            buf = extend(buf, rows)
            return buf, buf.sum()
        """,
        OPS,
    ),
    "FL-RACE-GUARD": (
        """
        import threading
        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock
            def size(self):
                return len(self._entries)
        """,
        """
        import threading
        class Cache:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock
            def size(self):
                with self._lock:
                    return len(self._entries)
        """,
        SERVICE,
    ),
    "FL-RACE-BLOCKING": (
        """
        import threading
        class Client:
            def __init__(self):
                self._lock = threading.Lock()
            def ping(self):
                with self._lock:
                    return self.request("ping", {})
        """,
        """
        import threading
        class Client:
            def __init__(self):
                self._lock = threading.Lock()
            def ping(self):
                with self._lock:
                    pending = True
                return self.request("ping", {})
        """,
        SERVICE,
    ),
    "FL-RACE-ORDER": (
        """
        import threading
        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
            def one(self):
                with self._a:
                    with self._b:
                        pass
            def two(self):
                with self._b:
                    with self._a:
                        pass
        """,
        """
        import threading
        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
            def one(self):
                with self._a:
                    with self._b:
                        pass
            def two(self):
                with self._a:
                    with self._b:
                        pass
        """,
        SERVICE,
    ),
    "FL-RACE-MUTITER": (
        """
        import threading
        class Reg:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock
            def sweep(self):
                with self._lock:
                    for key in self._entries:
                        self._entries.pop(key)
        """,
        """
        import threading
        class Reg:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock
            def sweep(self):
                with self._lock:
                    for key in list(self._entries):
                        self._entries.pop(key)
        """,
        SERVICE,
    ),
    "FL-RACE-CHECKACT": (
        """
        import threading
        class Reg:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock
            def put(self, k, v):
                with self._lock:
                    seen = k in self._entries
                if not seen:
                    with self._lock:
                        self._entries[k] = v
        """,
        """
        import threading
        class Reg:
            def __init__(self):
                self._lock = threading.Lock()
                self._entries = {}  # guarded-by: _lock
            def put(self, k, v):
                with self._lock:
                    if k not in self._entries:
                        self._entries[k] = v
        """,
        SERVICE,
    ),
    "FL-RACE-WAITFOREVER": (
        """
        import threading
        def run(flight):
            done = threading.Event()
            done.wait()
        """,
        """
        import threading
        def run(flight):
            done = threading.Event()
            if not done.wait(timeout=30.0):
                raise TimeoutError
        """,
        SERVICE,
    ),
    "FL-EVENT-EMITITER": (
        """
        class Emitter:
            def emit(self, event):
                for fn in self._listeners[event]:
                    fn(event)
        """,
        """
        class Emitter:
            def emit(self, event):
                for fn in list(self._listeners[event]):
                    fn(event)
        """,
        RUNTIME,
    ),
    "FL-LEAK-PAIR": (
        """
        class S:
            def work(self, key):
                status = self.cache.begin(key)
                tree = self.fold(key)
                self.cache.finish(key)
                return tree
        """,
        """
        class S:
            def work(self, key):
                status = self.cache.begin(key)
                try:
                    return self.fold(key)
                finally:
                    self.cache.abandon(key)
        """,
        SERVICE,
    ),
    "FL-LEAK-ESCAPE": (
        """
        import socket
        def probe(host):
            s = socket.create_connection((host, 1))
            data = s.recv(10)
            s.close()
            return data
        """,
        """
        import socket
        def probe(host):
            with socket.create_connection((host, 1)) as s:
                return s.recv(10)
        """,
        SERVICE,
    ),
    "FL-LEAK-SWALLOW": (
        """
        def loop(self):
            try:
                self.step()
            except Exception:
                pass
        """,
        """
        def loop(self):
            try:
                self.step()
            except Exception as exc:
                self.mc.logger.send({"eventName": "stepError",
                                     "error": str(exc)})
        """,
        SERVICE,
    ),
    "FL-LEAK-FINALLY-MASK": (
        """
        def f():
            try:
                work()
            finally:
                return 1
        """,
        """
        def f():
            try:
                work()
            finally:
                cleanup()
        """,
        SERVICE,
    ),
    "FL-LEAK-GEN-HOLD": (
        """
        def walk(self):
            with self._lock:
                for x in self._items:
                    yield x
        """,
        """
        def walk(self):
            with self._lock:
                snap = list(self._items)
            for x in snap:
                yield x
        """,
        SERVICE,
    ),
    "FL-LEAK-DOUBLE-CLOSE": (
        """
        class Session:
            def _write(self):
                self.close()
            def close(self):
                self.writer.close()
        """,
        """
        class Session:
            def _write(self):
                self.close()
            def close(self):
                if self._closed:
                    return
                self._closed = True
                self.writer.close()
        """,
        SERVICE,
    ),
    "FL-DUR-RENAME": (
        """
        import os
        def publish(tmp, path):
            with open(tmp, "wb") as f:
                f.write(b"data")
            os.replace(tmp, path)
        """,
        """
        import os
        def publish(tmp, path):
            with open(tmp, "wb") as f:
                f.write(b"data")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        """,
        SERVICE,
    ),
    "FL-DUR-COMMIT": (
        """
        class Log:
            def append(self, msg, client):
                client.ack(msg)
                self._file.write(msg)  # commit-point: op record
        """,
        """
        class Log:
            def append(self, msg, client):
                self._file.write(msg)  # commit-point: op record
                client.ack(msg)
        """,
        SERVICE,
    ),
    "FL-DUR-UNWIND": (
        """
        class Seq:
            def __init__(self):
                self._seq = 0  # durable-shadow: stamp counter
            def stamp(self, msg):
                self._seq += 1
                self._log.write(msg)  # unwinds: _seq
        """,
        """
        class Seq:
            def __init__(self):
                self._seq = 0  # durable-shadow: stamp counter
            def stamp(self, msg):
                self._seq += 1
                try:
                    self._log.write(msg)  # unwinds: _seq
                except Exception:
                    self._seq -= 1
                    raise
        """,
        SERVICE,
    ),
    "FL-DUR-TORN": (
        """
        import os
        class Log:
            def __init__(self, path):
                self._file = open(path, "wb")  # durable-handle: single-record
            def append(self, head, body):
                self._file.write(head)
                self._file.write(body)
                os.fsync(self._file.fileno())
        """,
        """
        import os
        class Log:
            def __init__(self, path):
                self._file = open(path, "wb")  # durable-handle: single-record
            def append(self, head, body):
                self._file.write(head + body)
                self._file.flush()
                os.fsync(self._file.fileno())
        """,
        SERVICE,
    ),
    "FL-ERR-CROSS": (
        """
        class Session:
            def respond(self, req):
                out = self._dispatch(req)
                return {"ok": True, "result": out}
        """,
        """
        class Session:
            def respond(self, req):
                try:
                    out = self._dispatch(req)
                except Exception as exc:
                    return {"ok": False, "code": "internal",
                            "error": str(exc)}
                return {"ok": True, "result": out}
        """,
        SERVICE,
    ),
    "FL-ERR-HANDLER": (
        """
        class Session:
            def respond(self, session, req):
                try:
                    payload = self._build(req)
                except Exception:
                    payload = None
                send_obj(session, payload)
        """,
        """
        class Session:
            def respond(self, session, req):
                try:
                    payload = self._build(req)
                except Exception as exc:
                    payload = {"ok": False, "code": "internal",
                               "error": str(exc)}
                send_obj(session, payload)
        """,
        SERVICE,
    ),
    "FL-KERN-BLOCK": (
        """
        from jax.experimental import pallas as pl
        def fold(x, D):
            spec = pl.BlockSpec((D, 100), lambda d: (d, 0))
            return spec
        """,
        """
        from jax.experimental import pallas as pl
        LANE = 128
        def _round_up(n, mult):
            return ((n + mult - 1) // mult) * mult
        def fold(x, D):
            Dp = _round_up(D, 8)
            spec = pl.BlockSpec((Dp, LANE), lambda d: (d, 0))
            return spec
        """,
        OPS,
    ),
    "FL-KERN-NARROW": (
        """
        import numpy as np
        def pack(vals):
            return np.asarray(vals).astype(np.int16)
        """,
        """
        import numpy as np
        I16_LIMIT = 32766
        def pack(vals, meta):
            if not meta.get("i16_ok"):
                raise ValueError("values exceed the narrow bound")
            return np.asarray(vals).astype(np.int16)
        """,
        OPS,
    ),
    "FL-KERN-BUCKET": (
        """
        import jax
        @jax.jit
        def _fold(x, n):
            return x[:n]
        def run(x, docs):
            return _fold(x, len(docs))
        """,
        """
        import jax
        from .interning import next_bucket
        @jax.jit
        def _fold(x, n):
            return x[:n]
        def run(x, docs):
            return _fold(x, next_bucket(len(docs)))
        """,
        OPS,
    ),
    "FL-KERN-PAD": (
        """
        import jax.numpy as jnp
        def digest(x):
            plane = jnp.pad(x, ((0, 3),))
            return plane.sum()
        """,
        """
        import jax.numpy as jnp
        def digest(x, mask):
            plane = jnp.pad(x, ((0, 3),))
            return jnp.where(mask, plane, 0).sum()
        """,
        OPS,
    ),
}


@pytest.mark.parametrize("rule", sorted(MODULE_RULE_FIXTURES))
def test_positive_fixture_fires(rule):
    bad, _good, relpath = MODULE_RULE_FIXTURES[rule]
    hits = findings_for(bad, relpath, rule)
    assert hits, f"{rule}: positive fixture produced no finding"
    assert all(f.line > 0 and f.message for f in hits)


@pytest.mark.parametrize("rule", sorted(MODULE_RULE_FIXTURES))
def test_negative_fixture_is_clean(rule):
    _bad, good, relpath = MODULE_RULE_FIXTURES[rule]
    assert findings_for(good, relpath, rule) == [], (
        f"{rule}: negative fixture flagged")


@pytest.mark.parametrize("rule", sorted(MODULE_RULE_FIXTURES))
def test_testing_dir_is_exempt(rule):
    bad, _good, _relpath = MODULE_RULE_FIXTURES[rule]
    assert findings_for(bad, TESTING, rule) == []


def test_setiter_reports_each_site_once():
    # a loop inside a def is visible from the module walk AND its own
    # scope walk; the walker must stop at scope boundaries or every
    # function-body site double-reports
    src = """
    def order():
        ids = {1, 2, 3}
        for i in ids:
            pass
    """
    assert len(findings_for(src, LOADER, "FL-DET-SETITER")) == 1


def test_setiter_checks_class_bodies():
    # class bodies are their own lexical scope; a hash-order-dependent
    # class attribute must not slip past the gate
    src = """
    class Registry:
        IDS = {"b", "a"}
        ORDER = [x for x in IDS]
    """
    assert len(findings_for(src, LOADER, "FL-DET-SETITER")) == 1


def test_trace_rules_do_not_fire_outside_kernel_scope():
    bad, _good, _ = MODULE_RULE_FIXTURES["FL-TRACE-HOSTSYNC"]
    assert findings_for(bad, LOADER, "FL-TRACE-HOSTSYNC") == []


def test_untraced_function_not_flagged():
    # host syncs in plain host-side code are fine — scope is traced defs
    src = """
    import numpy as np
    def host_extract(arr):
        return np.asarray(arr).tolist()
    """
    assert findings_for(src, OPS, "FL-TRACE-HOSTSYNC") == []


def test_hostsync_messages_are_function_scoped():
    # suppression keys are (rule, path, message): naming the owning def
    # keeps one reviewed suppression from masking a future host sync in
    # a different function of the same file
    src = """
    import jax
    @jax.jit
    def fold_a(x):
        return x.item()
    @jax.jit
    def fold_b(x):
        return x.item()
    """
    msgs = {f.message for f in findings_for(src, OPS, "FL-TRACE-HOSTSYNC")}
    assert len(msgs) == 2
    assert any("fold_a()" in m for m in msgs)
    assert any("fold_b()" in m for m in msgs)


def test_scan_argument_is_traced():
    # functions passed by name to lax.scan count as traced
    src = """
    import jax
    from jax import lax
    def step(carry, x):
        return carry + x.item(), x
    def fold(xs):
        return lax.scan(step, 0, xs)
    """
    assert findings_for(src, OPS, "FL-TRACE-HOSTSYNC")


def test_donate_assigned_jit_form_and_position():
    # f = jax.jit(g, donate_argnums=(1,)) donates position 1 ONLY: a
    # later read of the position-0 arg is fine, the donated one fires.
    src = """
    import jax
    def g(a, b):
        return a + b
    f = jax.jit(g, donate_argnums=(1,))
    def caller(a, b):
        out = f(a, b)
        keep = a.sum()
        return out, keep, b.sum()
    """
    msgs = [x.message for x in findings_for(src, OPS, "FL-TRACE-DONATE")]
    assert len(msgs) == 1 and "'b' was donated" in msgs[0], msgs


def test_donate_rebind_before_read_clears():
    # A Store between the donating call and the read re-points the name
    # at a live value — no finding.
    src = """
    import functools
    import jax
    @functools.partial(jax.jit, donate_argnums=(0,))
    def extend(buf, rows):
        return buf + rows
    def caller(buf, rows, fresh):
        out = extend(buf, rows)
        buf = fresh
        return out, buf.sum()
    """
    assert findings_for(src, OPS, "FL-TRACE-DONATE") == []


def test_donate_attribute_receiver_not_flagged():
    # Attribute receivers (entry.ops) are the documented limit: the
    # owner swaps the reference (the device-cache idiom) and the rule
    # stays silent rather than guessing aliasing.
    src = """
    import functools
    import jax
    @functools.partial(jax.jit, donate_argnums=(0,))
    def extend(buf, rows):
        return buf + rows
    def caller(entry, rows):
        entry.ops = extend(entry.ops, rows)
        return entry.ops.sum()
    """
    assert findings_for(src, OPS, "FL-TRACE-DONATE") == []


def test_donate_outside_kernel_scope_is_exempt():
    bad, _good, _path = MODULE_RULE_FIXTURES["FL-TRACE-DONATE"]
    assert findings_for(bad, LOADER, "FL-TRACE-DONATE") == []


# -- fluidrace: the concurrency family ---------------------------------------


RACE_PREAMBLE = """
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
"""


def test_race_guard_inferred_without_annotation():
    # all writes under one lock => the attribute is adopted as guarded;
    # the unlocked read is a finding even with no '# guarded-by' comment
    src = RACE_PREAMBLE + """
        self._n = 0
    def bump(self):
        with self._lock:
            self._n += 1
    def peek(self):
        return self._n
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1 and "peek()" in hits[0].message


def test_race_guard_ambiguous_multi_lock_inference_declined():
    # writes only in a `_locked` method of a two-lock class are "held
    # under ALL locks" — adopting either one would be a guess, flagging
    # correctly-locked reads against the wrong lock; such attrs need an
    # explicit declaration
    src = """
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._extend_lock = threading.Lock()
        self._n = 0
    def _bump_locked(self):
        self._n += 1
    def peek(self):
        with self._lock:
            return self._n
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_guard_mixed_lock_writes_not_inferred():
    # a write outside any lock makes the inference ambiguous — flagging
    # reads would be noise; only a declaration enforces such an attr
    src = RACE_PREAMBLE + """
        self._n = 0
    def bump(self):
        with self._lock:
            self._n += 1
    def reset(self):
        self._n = 0
    def peek(self):
        return self._n
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_guard_locked_suffix_and_holds_comment_exempt():
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _lock
    def _get_locked(self, k):
        return self._entries[k]
    def fetch(self, k):  # holds-lock: _lock
        return self._entries[k]
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_guard_holds_comment_may_follow_signature():
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _lock
    def fetch(self, k):
        # holds-lock: _lock
        return self._entries[k]
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_guard_unknown_lock_declaration_is_flagged():
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _mutex
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1 and "_mutex" in hits[0].message


def test_race_guard_unknown_holds_lock_annotation_is_flagged():
    # a typo'd '# holds-lock:' must not silently exempt the method (and
    # silently decline all-writes inference for what it writes)
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _lock
    def fetch(self, k):  # holds-lock: _lokc
        return self._entries[k]
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 2  # the bad annotation AND the unguarded read
    bad = [h for h in hits if "_lokc" in h.message]
    assert len(bad) == 1 and "fetch()" in bad[0].message


def test_race_guard_known_holds_lock_annotation_not_flagged():
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _lock
    def fetch(self, k):  # holds-lock: _lock
        return self._entries[k]
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_guard_deferred_closure_is_not_lock_held():
    # a callback defined under the lock RUNS later, without it
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _lock
        self._cb = None
    def kick(self):
        with self._lock:
            def cb():
                return self._entries
            self._cb = cb
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1
    assert "deferred callback" in hits[0].message
    assert "kick()" in hits[0].message


def test_race_guard_messages_are_function_scoped():
    src = RACE_PREAMBLE + """
        self._n = 0  # guarded-by: _lock
    def peek_a(self):
        return self._n
    def peek_b(self):
        return self._n
"""
    msgs = {f.message for f in findings_for(src, SERVICE, "FL-RACE-GUARD")}
    assert len(msgs) == 2
    assert any("peek_a()" in m for m in msgs)
    assert any("peek_b()" in m for m in msgs)


def test_race_single_threaded_class_is_not_analyzed():
    # no locks, no threads, no events: annotation-free and silent even
    # with "racy"-looking access patterns
    src = """
class Plain:
    def __init__(self):
        self._entries = {}
    def put(self, k, v):
        self._entries[k] = v
"""
    for rule in ("FL-RACE-GUARD", "FL-RACE-CHECKACT", "FL-RACE-MUTITER"):
        assert findings_for(src, SERVICE, rule) == []


def test_race_order_self_deadlock_on_nonreentrant_lock():
    src = RACE_PREAMBLE + """
    def oops(self):
        with self._lock:
            with self._lock:
                pass
"""
    hits = findings_for(src, SERVICE, "FL-RACE-ORDER")
    assert len(hits) == 1 and "non-reentrant" in hits[0].message


def test_race_order_rlock_self_nesting_allowed():
    src = """
import threading
class C:
    def __init__(self):
        self._lock = threading.RLock()
    def fine(self):
        with self._lock:
            with self._lock:
                pass
"""
    assert findings_for(src, SERVICE, "FL-RACE-ORDER") == []


def test_race_order_multi_item_with_acquires_sequentially():
    # `with a, b:` orders a before b, so an opposite nested order in
    # another method is a real cycle
    src = """
import threading
class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
    def one(self):
        with self._a, self._b:
            pass
    def two(self):
        with self._b:
            with self._a:
                pass
"""
    hits = findings_for(src, SERVICE, "FL-RACE-ORDER")
    assert len(hits) == 1 and "_a" in hits[0].message


def test_race_order_cycle_reported_once_per_class():
    bad, _good, _path = MODULE_RULE_FIXTURES["FL-RACE-ORDER"]
    hits = findings_for(bad, SERVICE, "FL-RACE-ORDER")
    assert len(hits) == 1
    assert "_a" in hits[0].message and "_b" in hits[0].message


def test_race_blocking_event_wait_under_lock():
    src = RACE_PREAMBLE + """
        self.done = threading.Event()
    def stall(self):
        with self._lock:
            self.done.wait(5)
"""
    hits = findings_for(src, SERVICE, "FL-RACE-BLOCKING")
    assert len(hits) == 1 and "stall()" in hits[0].message


LOOP_PREAMBLE = """
import selectors
class Pump:
    def __init__(self):
        self._sel = selectors.DefaultSelector()
"""


def test_race_blocking_on_loop_blocklist_call_no_lock_needed():
    # a selector-constructing class is an event-loop class: a blocklist
    # call in any of its methods fires with NO lock held — it stalls the
    # loop, not a lock contender
    src = LOOP_PREAMBLE + """
    def on_frame(self, conn, frame):
        return self.rpc.request("heads", {})
"""
    hits = findings_for(src, SERVICE, "FL-RACE-BLOCKING")
    assert len(hits) == 1 and "on-loop" in hits[0].message \
        and "on_frame()" in hits[0].message


def test_race_blocking_on_loop_exemptions():
    # the loop's own socket primitives (recv/accept) run non-blocking on
    # the loop by construction; '# off-loop' methods run on other
    # threads; a deferred lambda executes off-loop (that IS the fix);
    # __init__ runs before the loop exists
    src = LOOP_PREAMBLE + """
        self.rpc.request("hello", {})
    def service(self, key):
        data = key.fileobj.recv(65536)
        conn = self._lsock.accept()
        return data, conn
    def submit(self, pool, frame):
        pool.defer(lambda: self.rpc.request("fold", frame))
    def admin_stats(self):  # off-loop
        return self.rpc.request("stats", {})
"""
    assert findings_for(src, SERVICE, "FL-RACE-BLOCKING") == []


def test_race_blocking_on_loop_opt_in_marker():
    # '# on-loop' opts a method in even in a class that never constructs
    # a selector (e.g. a callback registered ON some other pump)
    src = """
import time
class Handler:
    def on_frame(self, conn, frame):  # on-loop
        time.sleep(1)
"""
    hits = findings_for(src, SERVICE, "FL-RACE-BLOCKING")
    assert len(hits) == 1 and "on-loop" in hits[0].message


def test_race_blocking_on_loop_under_lock_single_finding():
    # a call that is BOTH under a lock and on-loop yields one finding
    # (the under-lock message wins), never a duplicate pair
    src = """
import selectors, threading
class Pump:
    def __init__(self):
        self._sel = selectors.DefaultSelector()
        self._lock = threading.Lock()
    def on_frame(self, conn, frame):
        with self._lock:
            return self.rpc.request("heads", {})
"""
    hits = findings_for(src, SERVICE, "FL-RACE-BLOCKING")
    assert len(hits) == 1 and "holding" in hits[0].message


def test_race_blocking_event_wait_on_loop():
    # Event.wait inside an on-loop callback stalls the loop even with no
    # lock anywhere in sight
    src = """
import selectors, threading
class Pump:
    def __init__(self):
        self._sel = selectors.DefaultSelector()
        self.ready = threading.Event()
    def on_frame(self, conn, frame):
        self.ready.wait(5)
"""
    hits = findings_for(src, SERVICE, "FL-RACE-BLOCKING")
    assert len(hits) == 1 and "ready.wait" in hits[0].message


def test_race_class_level_lock_spelled_via_class_name():
    # `with C._serial:` inside class C counts as acquiring C's own lock
    src = """
import threading
class C:
    _serial = threading.RLock()
    def __init__(self):
        self.n = 0  # guarded-by: _serial
    def bump(self):
        with C._serial:
            self.n += 1
    def peek(self):
        return self.n
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1 and "peek()" in hits[0].message


def test_race_checkact_ignores_deferred_writes():
    # a callback DEFINED under the second acquisition does not mutate in
    # that critical section — no check-then-act
    src = RACE_PREAMBLE + """
        self._entries = {}  # guarded-by: _lock
        self._cb = None
    def arm(self, k):
        with self._lock:
            seen = k in self._entries
        if not seen:
            with self._lock:
                def cb():
                    self._entries[k] = 1
                self._cb = cb
"""
    assert findings_for(src, SERVICE, "FL-RACE-CHECKACT") == []


def test_race_non_lock_context_manager_not_adopted_as_lock():
    # `with self._file:` on an attr visibly assigned a non-lock must not
    # poison guard inference with a bogus '_file' lock
    src = """
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._file = open("/dev/null")
        self._n = 0
    def write_a(self):
        with self._file:
            self._n += 1
    def write_b(self):
        with self._file:
            self._n += 1
    def peek(self):
        return self._n
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_manual_acquire_method_exempt_not_flagged():
    # imperative lock.acquire()/try/finally-release flow is beyond the
    # lexical held-set: such methods are trusted, never false-positived
    src = RACE_PREAMBLE + """
        self._n = 0  # guarded-by: _lock
    def manual(self):
        self._lock.acquire()
        try:
            self._n = 1
        finally:
            self._lock.release()
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_method_local_lock_not_adopted_as_member():
    # `lk = threading.Lock()` inside a method is a local, not a class
    # lock; `with lk:` must not feed guard inference
    src = """
import threading
class C:
    def __init__(self):
        self._real = threading.Lock()
        self._n = 0
    def bump(self):
        lk = threading.Lock()
        with lk:
            self._n += 1
    def peek(self):
        return self._n
"""
    assert findings_for(src, SERVICE, "FL-RACE-GUARD") == []


def test_race_checkact_nested_reentrant_acquire_is_one_section():
    # an RLock re-acquired inside its own critical section never
    # releases in between — not a separate acquisition
    src = """
import threading
class C:
    def __init__(self):
        self._lock = threading.RLock()
        self._m = {}  # guarded-by: _lock
    def put(self):
        with self._lock:
            x = self._m.get(1)
            with self._lock:
                self._m[1] = 2
"""
    assert findings_for(src, SERVICE, "FL-RACE-CHECKACT") == []


def test_race_waitforever_only_on_serving_paths():
    bad, _good, _path = MODULE_RULE_FIXTURES["FL-RACE-WAITFOREVER"]
    assert findings_for(bad, RUNTIME, "FL-RACE-WAITFOREVER") == []


def test_race_annotated_lock_assignment_still_analyzed():
    # a type-hinted lock (AnnAssign) must not silently disable the class
    src = """
import threading
class C:
    def __init__(self):
        self._lock: threading.Lock = threading.Lock()
        self._m = {}  # guarded-by: _lock
    def peek(self):
        return self._m
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1 and "peek()" in hits[0].message


def test_race_nested_class_model_does_not_leak_into_enclosing():
    # Inner's lock + guarded-by must not make Outer thread-visible or
    # flag Outer's same-named attribute; Inner itself is still analyzed
    # (class_models builds a model per ClassDef, nested included)
    src = """
import threading
class Outer:
    def __init__(self):
        self._m = {}
    def touch(self):
        return self._m
    class Inner:
        def __init__(self):
            self._lock = threading.Lock()
            self._m = {}  # guarded-by: _lock
        def peek(self):
            return self._m
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1
    assert "Inner" in hits[0].message and "peek()" in hits[0].message


def test_race_bare_annotated_lock_declaration_recognized():
    # a value-less typed declaration (`_lock: threading.Lock`, assigned
    # by a base/harness) must keep the class thread-visible and serve as
    # a guard — not silently disable the whole analysis
    src = """
import threading
class C:
    _lock: threading.Lock
    def __init__(self):
        self._m = {}  # guarded-by: _lock
    def put(self, k, v):
        with self._lock:
            self._m[k] = v
    def peek(self):
        return self._m
"""
    hits = findings_for(src, SERVICE, "FL-RACE-GUARD")
    assert len(hits) == 1 and "peek()" in hits[0].message


def test_race_condition_wait_under_its_lock_not_blocking():
    # Condition.wait() REQUIRES the lock held (it releases internally):
    # the canonical pattern must not be a blocking-under-lock finding...
    src = """
import threading
class C:
    def __init__(self):
        self._cond = threading.Condition()
        self.ready = False
    def consume(self):
        with self._cond:
            while not self.ready:
                self._cond.wait(5.0)
"""
    assert findings_for(src, SERVICE, "FL-RACE-BLOCKING") == []
    # ...but a timeout-less Condition.wait() still hangs a crashed
    # notifier's waiters: FL-RACE-WAITFOREVER owns that case.
    src_no_timeout = src.replace("self._cond.wait(5.0)",
                                 "self._cond.wait()")
    hits = findings_for(src_no_timeout, SERVICE, "FL-RACE-WAITFOREVER")
    assert len(hits) == 1 and "consume()" in hits[0].message


def test_race_blocking_messages_survive_baseline_hygiene(tmp_path):
    # the bare-acquire message spells '.acquire()' dot-prefixed so a
    # reviewed suppression of it can actually pass the hygiene check
    src = RACE_PREAMBLE + """
        self._other = threading.Lock()
    def grab(self):
        with self._lock:
            self._other.acquire()
"""
    hits = findings_for(src, SERVICE, "FL-RACE-BLOCKING")
    assert len(hits) == 1 and ".acquire()" in hits[0].message
    pkg = tmp_path / "fluidframework_tpu" / "service"
    pkg.mkdir(parents=True)
    (pkg / "x.py").write_text(textwrap.dedent(src))
    entry = {"rule": "FL-RACE-BLOCKING", "path": SERVICE,
             "message": hits[0].message, "reason": "reviewed"}
    assert baseline_function_hygiene(tmp_path, [entry]) == []


# -- project rule: FL-WIRE-COMPLETE ------------------------------------------


def _write_wire_tree(root, wire_body, test_body=None):
    proto = root / "fluidframework_tpu" / "protocol"
    proto.mkdir(parents=True)
    (proto / "messages.py").write_text(textwrap.dedent("""
        import dataclasses

        @dataclasses.dataclass
        class PingMessage:
            seq: int
    """))
    (proto / "wire.py").write_text(textwrap.dedent(wire_body))
    if test_body is not None:
        tdir = root / "tests"
        tdir.mkdir()
        (tdir / "test_wire_roundtrip.py").write_text(
            textwrap.dedent(test_body))


COMPLETE_WIRE = """
    def encode_ping_message(m): return {"seq": m.seq}
    def decode_ping_message(d): return d["seq"]
    MESSAGE_CODECS = {"PingMessage": (encode_ping_message,
                                      decode_ping_message)}
"""


def test_wire_complete_positive(tmp_path):
    _write_wire_tree(tmp_path, "MESSAGE_CODECS = {}\n", test_body="x = 1\n")
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-WIRE-COMPLETE"}
    assert any("encode_ping_message" in m for m in msgs), msgs
    assert any("decode_ping_message" in m for m in msgs), msgs
    assert any("MESSAGE_CODECS" in m for m in msgs), msgs
    assert any("round-trip coverage" in m for m in msgs), msgs


def test_wire_complete_negative(tmp_path):
    _write_wire_tree(tmp_path, COMPLETE_WIRE,
                     test_body="from x import PingMessage\n")
    assert [f for f in analyze(tmp_path)
            if f.rule == "FL-WIRE-COMPLETE"] == []


def test_project_rules_skipped_on_path_scoped_runs(tmp_path):
    # whole-repo contracts don't belong to a "files I touched" run (and
    # their suppressions would be filtered out of scope with them)
    _write_wire_tree(tmp_path, "MESSAGE_CODECS = {}\n", test_body="x = 1\n")
    scoped = analyze(tmp_path,
                     relpaths=["fluidframework_tpu/protocol/messages.py"])
    assert [f for f in scoped if f.rule == "FL-WIRE-COMPLETE"] == []


def test_wire_complete_missing_test_suite(tmp_path):
    _write_wire_tree(tmp_path, COMPLETE_WIRE, test_body=None)
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-WIRE-COMPLETE"}
    assert any("no tests/test_wire*.py" in m for m in msgs), msgs


def test_wire_complete_covers_wire_module_dataclasses(tmp_path):
    """A dataclass defined in wire.py ITSELF (the columnar batch forms)
    carries the same codec + registry + round-trip obligations as one in
    messages.py."""
    _write_wire_tree(tmp_path, COMPLETE_WIRE + """
    import dataclasses

    @dataclasses.dataclass(eq=False)
    class ColumnBatch:
        packed: bytes
""", test_body="from x import PingMessage\n")
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-WIRE-COMPLETE"}
    assert any("encode_column_batch" in m for m in msgs), msgs
    assert any("decode_column_batch" in m for m in msgs), msgs
    assert any("ColumnBatch is not registered" in m for m in msgs), msgs
    assert any("ColumnBatch has no round-trip coverage" in m
               for m in msgs), msgs


def test_wire_complete_wire_dataclass_negative(tmp_path):
    _write_wire_tree(tmp_path, """
    import dataclasses

    @dataclasses.dataclass(eq=False)
    class ColumnBatch:
        packed: bytes

    def encode_ping_message(m): return {"seq": m.seq}
    def decode_ping_message(d): return d["seq"]
    def encode_column_batch(b): return {"packed": b.packed}
    def decode_column_batch(d): return ColumnBatch(d["packed"])
    MESSAGE_CODECS = {"PingMessage": (encode_ping_message,
                                      decode_ping_message),
                      "ColumnBatch": (encode_column_batch,
                                      decode_column_batch)}
""", test_body="from x import PingMessage, ColumnBatch\n")
    assert [f for f in analyze(tmp_path)
            if f.rule == "FL-WIRE-COMPLETE"] == []


# -- baseline machinery ------------------------------------------------------


def _finding(msg="m1"):
    return Finding("FL-DET-CLOCK", "error", "pkg/a.py", 10, msg)


def _entry(msg="m1", reason="reviewed: fixture"):
    return {"rule": "FL-DET-CLOCK", "path": "pkg/a.py",
            "message": msg, "reason": reason}


def test_baseline_suppresses_by_rule_path_message():
    report = apply_baseline([_finding()], [_entry()])
    assert report.clean
    assert len(report.suppressed) == 1


def test_baseline_is_line_independent():
    moved = Finding("FL-DET-CLOCK", "error", "pkg/a.py", 99, "m1")
    assert apply_baseline([moved], [_entry()]).clean


def test_stale_suppression_fails_gate():
    report = apply_baseline([], [_entry()])
    assert not report.clean
    assert report.stale == [_entry()]


def test_reasonless_suppression_fails_gate():
    report = apply_baseline([_finding()], [_entry(reason="  ")])
    assert not report.clean
    assert report.invalid


def test_unsuppressed_finding_fails_gate():
    report = apply_baseline([_finding("other")], [_entry()])
    assert not report.clean
    assert [f.message for f in report.unsuppressed] == ["other"]


def test_missing_baseline_path_is_a_usage_error(tmp_path):
    from tools.fluidlint.cli import main
    assert main(["--root", str(tmp_path),
                 "--baseline", "lint_baseline.json"]) == 2


def test_path_scoped_run_ignores_out_of_scope_suppressions(tmp_path):
    # linting one clean file must not go red because the baseline also
    # covers findings in files outside the analyzed subset
    from tools.fluidlint.cli import main
    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "clean.py").write_text("x = 1\n")
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "FL-DET-CLOCK",
         "path": "fluidframework_tpu/loader/other.py",
         "message": "m", "reason": "reviewed"}]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "fluidframework_tpu/loader/clean.py"]) == 0


def test_path_arguments_are_normalized_against_root(tmp_path, capsys):
    # a './'-spelled path must hit the same rule scopes as the canonical
    # repo-relative form, not silently match nothing and pass
    from tools.fluidlint.cli import main
    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    rc = main(["--root", str(tmp_path),
               "./fluidframework_tpu/loader/bad.py"])
    assert rc == 1
    assert "FL-DET-CLOCK" in capsys.readouterr().out
    assert main(["--root", str(tmp_path), "/etc/passwd"]) == 2


def test_path_scoped_run_ignores_project_rule_suppressions(tmp_path):
    # analyze() skips project rules on scoped runs, so their reviewed
    # suppressions must not surface as stale
    from tools.fluidlint.cli import main
    pkg = tmp_path / "fluidframework_tpu" / "protocol"
    pkg.mkdir(parents=True)
    (pkg / "wire.py").write_text("x = 1\n")
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "FL-WIRE-COMPLETE",
         "path": "fluidframework_tpu/protocol/wire.py",
         "message": "m", "reason": "reviewed"}]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "fluidframework_tpu/protocol/wire.py"]) == 0


def test_directory_path_argument_expands_to_py_files(tmp_path, capsys):
    from tools.fluidlint.cli import main
    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    assert main(["--root", str(tmp_path), "fluidframework_tpu"]) == 1
    assert "FL-DET-CLOCK" in capsys.readouterr().out


def test_duplicate_baseline_entries_are_invalid():
    report = apply_baseline([_finding()], [_entry(), _entry()])
    assert not report.clean
    assert any("duplicate" in m for m in report.invalid)
    assert report.stale == []


def test_invalid_entry_not_double_reported_as_stale():
    report = apply_baseline([], [{"rule": "FL-DET-CLOCK",
                                  "message": "m", "reason": "r"}])
    assert report.invalid
    assert report.stale == []


def test_load_baseline_rejects_non_object(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps(["not", "an", "object"]))
    with pytest.raises(ValueError):
        load_baseline(p)


# -- baseline function hygiene ------------------------------------------------


def _hygiene_tree(tmp_path, body="def hold():\n    return 1\n"):
    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(body)
    return "fluidframework_tpu/loader/mod.py"


def _hygiene_entry(path, msg):
    return {"rule": "FL-DET-CLOCK", "path": path, "message": msg,
            "reason": "reviewed"}


def test_hygiene_flags_entry_for_deleted_function(tmp_path):
    path = _hygiene_tree(tmp_path)
    entries = [_hygiene_entry(path, "wall-clock read in vanished()")]
    problems = baseline_function_hygiene(tmp_path, entries)
    assert len(problems) == 1 and "vanished" in problems[0]


def test_hygiene_accepts_live_function_reference(tmp_path):
    path = _hygiene_tree(tmp_path)
    entries = [_hygiene_entry(path, "wall-clock read in hold()")]
    assert baseline_function_hygiene(tmp_path, entries) == []


def test_hygiene_ignores_builtins_and_dotted_calls(tmp_path):
    # "time.time()" names an API, "int()" a builtin — neither is a
    # function-scoped key; only bare local names count
    path = _hygiene_tree(tmp_path)
    entries = [_hygiene_entry(
        path, "int() via time.time() then str.join() somewhere")]
    assert baseline_function_hygiene(tmp_path, entries) == []


def test_hygiene_flags_entry_for_deleted_file(tmp_path):
    _hygiene_tree(tmp_path)
    entries = [_hygiene_entry("fluidframework_tpu/loader/gone.py",
                              "wall-clock read in hold()")]
    problems = baseline_function_hygiene(tmp_path, entries)
    assert len(problems) == 1 and "no longer exists" in problems[0]


def test_hygiene_fails_the_cli_gate(tmp_path, capsys):
    from tools.fluidlint.cli import main
    path = _hygiene_tree(tmp_path)
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        # message matches nothing AND names a dead function: surface the
        # hygiene diagnostic alongside staleness, and fail
        _hygiene_entry(path, "wall-clock read in vanished()")]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp)]) == 1
    out = capsys.readouterr().out
    assert "vanished" in out and "hygiene" in out


def test_check_baseline_mode_runs_without_analysis(tmp_path, capsys):
    from tools.fluidlint.cli import main
    path = _hygiene_tree(tmp_path)
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        _hygiene_entry(path, "wall-clock read in hold()")]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "--check-baseline"]) == 0
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        _hygiene_entry(path, "wall-clock read in vanished()")]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "--check-baseline"]) == 1


# -- CLI: --rules family filtering & --json -----------------------------------


def _clock_violation_tree(tmp_path):
    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")


def test_rules_filter_excludes_other_families(tmp_path, capsys):
    from tools.fluidlint.cli import main
    _clock_violation_tree(tmp_path)
    # The clock violation is invisible to a FL-RACE-only run...
    assert main(["--root", str(tmp_path), "--rules", "FL-RACE"]) == 0
    capsys.readouterr()
    # ...and still red for the family that owns it (prefix match).
    assert main(["--root", str(tmp_path), "--rules", "FL-DET"]) == 1
    assert "FL-DET-CLOCK" in capsys.readouterr().out


def test_rules_filter_spares_out_of_family_suppressions(tmp_path):
    # entries for unselected rules are ignored, not reported stale
    from tools.fluidlint.cli import main
    _clock_violation_tree(tmp_path)
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "FL-DET-CLOCK",
         "path": "fluidframework_tpu/loader/bad.py",
         "message": "m-that-matches-nothing", "reason": "reviewed"}]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "--rules", "FL-RACE"]) == 0


def test_rules_filter_rejects_unknown_family(tmp_path):
    from tools.fluidlint.cli import main
    _clock_violation_tree(tmp_path)
    assert main(["--root", str(tmp_path), "--rules", "FL-NOPE"]) == 2


def test_json_flag_emits_machine_readable_report(tmp_path, capsys):
    from tools.fluidlint.cli import main
    _clock_violation_tree(tmp_path)
    assert main(["--root", str(tmp_path), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["unsuppressed"], doc
    assert doc["unsuppressed"][0]["rule"] == "FL-DET-CLOCK"
    assert set(doc) == {"unsuppressed", "suppressed", "stale_suppressions",
                       "invalid_suppressions", "baseline_hygiene"}


# -- fluidleak: exit-path enumerator ------------------------------------------


def _parse_fn(src):
    import ast
    return ast.parse(textwrap.dedent(src)).body[0]


def test_exit_paths_enumerate_every_exit_kind():
    from tools.fluidlint.core import iter_exit_paths
    fn = _parse_fn("""
    def f(x):
        a = probe()
        if x:
            return 1
        raise ValueError("no")
    """)
    kinds = {p.kind for p in iter_exit_paths(fn)}
    # probe()/ValueError() may raise ("exception"), the explicit raise is
    # "raise", the if-true arm is "return"; no path falls off the end.
    assert kinds == {"return", "raise", "exception"}


def test_exit_paths_fall_through_records_calls_in_order():
    from tools.fluidlint.core import iter_exit_paths
    fn = _parse_fn("""
    def f():
        first()
        second()
    """)
    falls = [p for p in iter_exit_paths(fn) if p.kind == "fall"]
    assert len(falls) == 1
    names = [ev.node.func.id for ev in falls[0].events
             if ev.kind == "call"]
    assert names == ["first", "second"]


def test_exit_paths_finally_runs_on_exception_flows():
    from tools.fluidlint.core import iter_exit_paths
    fn = _parse_fn("""
    def f(res):
        res.start()
        try:
            work()
        finally:
            res.stop()
    """)
    def attr(ev):
        return getattr(ev.node.func, "attr", None)

    for p in iter_exit_paths(fn):
        started = [i for i, ev in enumerate(p.events)
                   if ev.kind == "call" and attr(ev) == "start"]
        if not started:
            continue  # start() itself raised
        assert any(attr(ev) == "stop"
                   for ev in p.events[started[0] + 1:]
                   if ev.kind in ("call", "call-raised")), (
            f"path exiting via {p.kind} never reached the finally")


def test_exit_paths_decline_over_budget():
    from tools.fluidlint.core import iter_exit_paths
    body = "".join(f"    if a{i}():\n        b{i}()\n" for i in range(64))
    fn = _parse_fn("def f():\n" + body)
    assert iter_exit_paths(fn) is None


def test_pair_rule_declines_over_budget_instead_of_guessing():
    # an opener followed by pathological branching: the enumerator
    # declines, so the rule reports NOTHING (never guesses)
    body = "".join(f"    if a{i}():\n        b{i}()\n" for i in range(64))
    src = ("class S:\n    def work(self, k):\n"
           "        self.cache.begin(k)\n" + body.replace("    ", "        "))
    assert findings_for(src, SERVICE, "FL-LEAK-PAIR") == []


# -- fluidleak: FL-LEAK-PAIR edges --------------------------------------------


def test_pair_closer_on_every_branch_is_clean():
    src = """
    class S:
        def work(self, k):
            h = self.c.begin(k)
            if h:
                self.c.finish(k)
            else:
                self.c.abandon(k)
    """
    assert findings_for(src, SERVICE, "FL-LEAK-PAIR") == []


def test_pair_executor_shutdown_keywords_not_an_opener():
    # shutdown->close is the SOCKET pair; Executor.shutdown(wait=...) is
    # itself terminal (keyword args mark the executor signature) while a
    # bare socket shutdown(how) still demands its close
    good = """
    class S:
        def stop(self):
            self.pool.shutdown(wait=False)
    """
    bad = """
    import socket
    class S:
        def stop(self):
            self.sock.shutdown(socket.SHUT_RDWR)
    """
    assert findings_for(good, SERVICE, "FL-LEAK-PAIR") == []
    assert findings_for(bad, SERVICE, "FL-LEAK-PAIR")


def test_pair_closer_on_one_branch_only_fires():
    src = """
    class S:
        def work(self, k):
            h = self.c.begin(k)
            if h:
                self.c.finish(k)
    """
    hits = findings_for(src, SERVICE, "FL-LEAK-PAIR")
    assert hits and "begin" in hits[0].message


def test_pair_receiver_must_match():
    # closing a DIFFERENT receiver's protocol does not close this one
    src = """
    class S:
        def work(self, k):
            self.c.begin(k)
            self.other.finish(k)
    """
    assert findings_for(src, SERVICE, "FL-LEAK-PAIR")


def test_pair_pairs_with_annotation_declares_site_specific_closers():
    bad = """
    class S:
        def work(self, key):
            h = self.store.grab(key)  # pairs-with: put_back, drop
            return self.fold(h)
    """
    good = """
    class S:
        def work(self, key):
            h = self.store.grab(key)  # pairs-with: put_back, drop
            try:
                return self.fold(h)
            finally:
                self.store.drop(key)
    """
    assert findings_for(bad, SERVICE, "FL-LEAK-PAIR")
    assert findings_for(good, SERVICE, "FL-LEAK-PAIR") == []


def test_pair_with_statement_counts_as_closed():
    src = """
    class S:
        def work(self, k):
            with self.pool.acquire(k) as conn:
                return conn.run()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-PAIR") == []


def test_pair_imperative_lock_requires_release():
    bad = """
    class S:
        def work(self):
            self._lock.acquire()
            return self.compute()
    """
    good = """
    class S:
        def work(self):
            self._lock.acquire()
            try:
                return self.compute()
            finally:
                self._lock.release()
    """
    assert findings_for(bad, SERVICE, "FL-LEAK-PAIR")
    assert findings_for(good, SERVICE, "FL-LEAK-PAIR") == []


def test_exit_paths_break_escaping_a_finally_to_outer_loop():
    # regression: break/continue flow items are bare event tuples — the
    # finally re-threading used to index them as (events, node) pairs
    # and crash the whole analyze() run with a TypeError
    from tools.fluidlint.core import iter_exit_paths
    fn = _parse_fn("""
    def f(self, items):
        for x in items:
            try:
                if x:
                    break
                if not x:
                    continue
            finally:
                cleanup(x)
        done()
    """)
    paths = iter_exit_paths(fn)
    assert paths is not None
    falls = [p for p in paths if p.kind == "fall"]
    assert falls, "break out of the loop must still fall off the end"
    # ...and the escaping break ran the finally before leaving the try
    names = [[getattr(ev.node.func, "id", None) for ev in p.events
              if ev.kind == "call"] for p in falls]
    assert any("cleanup" in seq and "done" in seq for seq in names)


def test_pair_break_through_finally_is_analyzed_not_crashed():
    src = """
    class S:
        def work(self, items):
            self._lock.acquire()
            try:
                for x in items:
                    try:
                        if x:
                            break
                    finally:
                        self.note(x)
            finally:
                self._lock.release()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-PAIR") == []


def test_pair_match_case_arms_branch_not_flatten():
    # regression: match fell into the plain-statement branch, flattening
    # case bodies into straight-line code — a closer in ONE arm looked
    # unconditional and a leaking arm's early return was invisible
    bad = """
    class S:
        def work(self, k):
            self.cache.begin(k)
            match k:
                case 0:
                    return None
                case _:
                    self.cache.finish(k)
    """
    good = """
    class S:
        def work(self, k):
            self.cache.begin(k)
            match k:
                case 0:
                    self.cache.abandon(k)
                case _:
                    self.cache.finish(k)
    """
    hits = findings_for(bad, SERVICE, "FL-LEAK-PAIR")
    assert hits and "begin" in hits[0].message
    assert findings_for(good, SERVICE, "FL-LEAK-PAIR") == []


def test_pair_non_exhaustive_match_keeps_fall_through_path():
    # no wildcard arm: no case may match, so the closer inside the only
    # arm does not cover the fall-through path
    src = """
    class S:
        def work(self, k):
            self.cache.begin(k)
            match k:
                case 0:
                    self.cache.finish(k)
    """
    assert findings_for(src, SERVICE, "FL-LEAK-PAIR")


# -- fluidleak: FL-LEAK-ESCAPE edges ------------------------------------------


def test_escape_handoff_to_self_is_not_a_leak():
    src = """
    import socket
    class C:
        def connect(self, host):
            s = socket.create_connection((host, 1))
            self._sock = s
    """
    assert findings_for(src, SERVICE, "FL-LEAK-ESCAPE") == []


def test_escape_handoff_as_argument_is_not_a_leak():
    src = """
    import socket
    def connect(pool, host):
        s = socket.create_connection((host, 1))
        pool.adopt(s)
    """
    assert findings_for(src, SERVICE, "FL-LEAK-ESCAPE") == []


def test_escape_daemon_thread_is_exempt_nondaemon_is_not():
    daemon = """
    import threading
    def run(fn):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
    """
    plain = """
    import threading
    def run(fn):
        t = threading.Thread(target=fn)
        t.start()
    """
    assert findings_for(daemon, SERVICE, "FL-LEAK-ESCAPE") == []
    assert findings_for(plain, SERVICE, "FL-LEAK-ESCAPE")


def test_escape_close_in_finally_is_clean():
    src = """
    def read(path):
        f = open(path)
        try:
            return f.read()
        finally:
            f.close()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-ESCAPE") == []


def test_escape_popen_is_tracked_positive_and_negative():
    """ISSUE 12 satellite: subprocess.Popen is a tracked resource — a
    fire-and-forget child process (zombie + leaked pipes) fires; reaping
    on all paths (try/finally wait or terminate) and the supervisor
    hand-off shape (stored on self) stay clean."""
    bad = """
    import subprocess
    def probe(cmd):
        p = subprocess.Popen(cmd)
        return p.stdout.read()
    """
    reaped = """
    import subprocess
    def probe(cmd):
        p = subprocess.Popen(cmd)
        try:
            return p.stdout.read()
        finally:
            p.wait()
    """
    killed = """
    import subprocess
    def probe(cmd):
        p = subprocess.Popen(cmd)
        try:
            return p.stdout.read()
        finally:
            p.kill()
    """
    handed_off = """
    import subprocess
    class Supervisor:
        def spawn(self, cmd):
            p = subprocess.Popen(cmd)
            self._shards.append(p)
    """
    assert findings_for(bad, SERVICE, "FL-LEAK-ESCAPE")
    assert findings_for(reaped, SERVICE, "FL-LEAK-ESCAPE") == []
    assert findings_for(killed, SERVICE, "FL-LEAK-ESCAPE") == []
    assert findings_for(handed_off, SERVICE, "FL-LEAK-ESCAPE") == []


def test_escape_makefile_needs_close():
    bad = """
    class C:
        def loop(self):
            rfile = self._sock.makefile("rb")
            return rfile.read(4)
    """
    good = """
    class C:
        def loop(self):
            rfile = self._sock.makefile("rb")
            try:
                return rfile.read(4)
            finally:
                rfile.close()
    """
    assert findings_for(bad, SERVICE, "FL-LEAK-ESCAPE")
    assert findings_for(good, SERVICE, "FL-LEAK-ESCAPE") == []


# -- fluidleak: FL-LEAK-SWALLOW edges -----------------------------------------


def test_swallow_bare_except_fires():
    src = """
    def loop(self):
        try:
            self.step()
        except:
            self.count += 1
    """
    assert findings_for(src, SERVICE, "FL-LEAK-SWALLOW")


def test_swallow_reraise_is_clean():
    src = """
    def loop(self):
        try:
            self.step()
        except Exception:
            self.rollback()
            raise
    """
    assert findings_for(src, SERVICE, "FL-LEAK-SWALLOW") == []


def test_swallow_narrow_exception_is_clean():
    src = """
    def loop(self):
        try:
            self.step()
        except KeyError:
            pass
    """
    assert findings_for(src, SERVICE, "FL-LEAK-SWALLOW") == []


def test_swallow_tuple_broad_except_fires():
    """`except (Exception, ValueError):` is the same front door as
    `except Exception:` — the tuple spelling must not slip the gate."""
    src = """
    def loop(self):
        try:
            self.step()
        except (Exception, ValueError):
            pass
    """
    assert findings_for(src, SERVICE, "FL-LEAK-SWALLOW")
    narrow = """
    def loop(self):
        try:
            self.step()
        except (KeyError, ValueError):
            pass
    """
    assert findings_for(narrow, SERVICE, "FL-LEAK-SWALLOW") == []


def test_swallow_sink_names_match_whole_words_only():
    """A bare call only counts as a telemetry sink when a whole
    underscore-word says so: 'update_backlog'/'login'/'catalog' merely
    CONTAIN 'log' and must not launder the swallow, while a real
    'log_event'/'warn' direct call still does."""
    for decoy in ("self.update_backlog()", "self.login()", "catalog()",
                  "self.backlog.put(1)"):
        src = f"""
        def loop(self):
            try:
                self.step()
            except Exception:
                {decoy}
        """
        assert findings_for(src, SERVICE, "FL-LEAK-SWALLOW"), decoy
    for sink in ("log_event('stepError')", "warn('stepError')"):
        src = f"""
        def loop(self):
            try:
                self.step()
            except Exception:
                {sink}
        """
        assert findings_for(src, SERVICE, "FL-LEAK-SWALLOW") == [], sink


def test_swallow_scope_is_serving_paths_only():
    bad, _good, _ = MODULE_RULE_FIXTURES["FL-LEAK-SWALLOW"]
    assert findings_for(bad, RUNTIME, "FL-LEAK-SWALLOW") == []


# -- fluidleak: FL-LEAK-FINALLY-MASK edges ------------------------------------


def test_finally_mask_bare_reraise_is_fine():
    src = """
    def f():
        try:
            work()
        except Exception:
            raise
        finally:
            try:
                cleanup()
            except OSError:
                raise
    """
    # `raise` with no exception re-raises; only `raise X` masks
    assert findings_for(src, SERVICE, "FL-LEAK-FINALLY-MASK") == []


def test_finally_mask_break_inside_local_loop_is_fine():
    src = """
    def f(items):
        try:
            work()
        finally:
            for x in items:
                if x:
                    break
    """
    assert findings_for(src, SERVICE, "FL-LEAK-FINALLY-MASK") == []


def test_finally_mask_continue_fires():
    src = """
    def f(items):
        for x in items:
            try:
                work(x)
            finally:
                continue
    """
    assert findings_for(src, SERVICE, "FL-LEAK-FINALLY-MASK")


def test_finally_mask_nested_try_reported_once():
    """A try/finally nested inside an outer finally must not double-
    report: the outer finalbody walk already covers it, and check()'s
    direct visit of the inner Try has to be skipped."""
    src = """
    def f():
        try:
            a()
        finally:
            try:
                b()
            finally:
                return 1
    """
    found = findings_for(src, SERVICE, "FL-LEAK-FINALLY-MASK")
    assert len(found) == 1, [f.message for f in found]


def test_finally_mask_caught_raise_inside_finally_is_fine():
    """A raise inside a finally-local try WITH handlers is assumed
    caught before it can mask the in-flight exception; the same raise
    in a handler or orelse body stays unprotected and fires."""
    src = """
    def f():
        try:
            work()
        finally:
            try:
                raise ValueError("probe")
            except ValueError:
                cleanup()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-FINALLY-MASK") == []
    src_handler = """
    def f():
        try:
            work()
        finally:
            try:
                cleanup()
            except OSError:
                raise RuntimeError("masks")
    """
    assert findings_for(src_handler, SERVICE, "FL-LEAK-FINALLY-MASK")


# -- fluidleak: FL-LEAK-GEN-HOLD edges ----------------------------------------


def test_gen_hold_open_file_handle_fires():
    src = """
    def lines(path):
        with open(path) as f:
            for line in f:
                yield line
    """
    assert findings_for(src, SERVICE, "FL-LEAK-GEN-HOLD")


def test_gen_hold_non_resource_context_is_fine():
    src = """
    def rows(self):
        with self.profiler:
            for r in self._rows:
                yield r
    """
    assert findings_for(src, SERVICE, "FL-LEAK-GEN-HOLD") == []


# -- fluidleak: FL-LEAK-DOUBLE-CLOSE edges ------------------------------------


def test_double_close_two_tracked_call_sites_fire():
    src = """
    class C:
        def close(self):
            self._file.close()
    def teardown():
        c = C()
        c.close()
        c.close()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-DOUBLE-CLOSE")


def test_double_close_single_call_site_is_quiet():
    src = """
    class C:
        def close(self):
            self._file.close()
    def teardown():
        c = C()
        c.close()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-DOUBLE-CLOSE") == []


def test_double_close_try_except_guard_accepted():
    # the _RpcClient shape: every release individually armored
    src = """
    class C:
        def reset(self):
            self.close()
        def close(self):
            try:
                self._sock.shutdown()
            except OSError:
                pass
            try:
                self._sock.close()
            except OSError:
                pass
    """
    assert findings_for(src, SERVICE, "FL-LEAK-DOUBLE-CLOSE") == []


def test_double_close_lock_wrapped_guard_accepted():
    # the OTHER _RpcClient shape: the idempotency flag is checked and
    # set under the state lock; the guard must be seen through `with`
    src = """
    class C:
        def reset(self):
            self.close()
        def close(self):
            with self._state_lock:
                if self._closed:
                    return
                self._closed = True
            self._writer.close()
    """
    assert findings_for(src, SERVICE, "FL-LEAK-DOUBLE-CLOSE") == []


# -- fluiddur behavior details -----------------------------------------------


def test_dur_rename_flags_os_rename_and_unflushed_fsync():
    src = """
    import os
    def publish(tmp_path, path, f):
        f.write(b"data")
        os.fsync(f.fileno())
        os.rename(tmp_path, path)
    """
    msgs = [f.message for f in findings_for(src, SERVICE, "FL-DUR-RENAME")]
    assert any("use os.replace()" in m for m in msgs), msgs
    assert any("without a preceding .flush()" in m for m in msgs), msgs


def test_dur_rename_tmpness_through_local_assignment():
    # the publish source is tmp-ish only via the local name it was
    # assigned from — the rule must chase one level of assignment
    src = """
    import os
    def publish(base, path):
        staging = base + ".tmp"
        os.replace(staging, path)
    """
    hits = findings_for(src, SERVICE, "FL-DUR-RENAME")
    assert len(hits) == 1 and "no os.fsync()" in hits[0].message


def test_dur_commit_annotation_requires_a_call():
    src = """
    class Log:
        def append(self, msg):
            pending = True  # commit-point: op record
            self._file.write(msg)
    """
    hits = findings_for(src, SERVICE, "FL-DUR-COMMIT")
    assert len(hits) == 1 and "no call" in hits[0].message


def test_dur_commit_names_the_label():
    src = """
    class Log:
        def append(self, msg, client):
            client.broadcast(msg)
            self._file.write(msg)  # commit-point: op record
    """
    hits = findings_for(src, SERVICE, "FL-DUR-COMMIT")
    assert len(hits) == 1
    assert "broadcast" in hits[0].message
    assert "op record" in hits[0].message


def test_dur_unwind_unknown_attribute_is_flagged():
    src = """
    class Seq:
        def __init__(self):
            self._seq = 0  # durable-shadow: stamp counter
        def stamp(self, msg):
            try:
                self._log.write(msg)  # unwinds: _sqe
            except Exception:
                raise
    """
    hits = findings_for(src, SERVICE, "FL-DUR-UNWIND")
    assert len(hits) == 1 and "_sqe" in hits[0].message
    assert "not declared" in hits[0].message


def test_dur_unwind_bare_commit_point_needs_pairing():
    src = """
    class Seq:
        def __init__(self):
            self._seq = 0  # durable-shadow: stamp counter
        def stamp(self, msg):
            self._seq += 1
            self._log.write(msg)  # commit-point: stamp record
    """
    hits = findings_for(src, SERVICE, "FL-DUR-UNWIND")
    assert len(hits) == 1
    assert "no '# unwinds:' pairing" in hits[0].message


def test_dur_unwind_restores_through_alias_and_helper():
    # the two real restore shapes: a subscript store through a local
    # alias of the shadow attr, and a one-level same-class helper call
    src = """
    class Seq:
        def __init__(self):
            self._docs = {}  # durable-shadow: log view
            self._slots = {}  # durable-shadow: membership
        def _drop(self, cid):
            self._slots = {}
        def stamp(self, cid, msg):
            log = self._docs.setdefault(cid, [])
            log.append(msg)
            self._slots[cid] = 1
            try:
                self._file.write(msg)  # unwinds: _docs, _slots
            except Exception:
                log.pop()
                self._drop(cid)
                raise
    """
    assert findings_for(src, SERVICE, "FL-DUR-UNWIND") == []
    # drop the helper call: _slots is no longer restored
    broken = src.replace("                self._drop(cid)\n", "")
    hits = findings_for(broken, SERVICE, "FL-DUR-UNWIND")
    assert len(hits) == 1 and "'_slots'" in hits[0].message


def test_dur_torn_same_class_fsync_helper_is_an_fsync_point():
    src = """
    import os
    class Log:
        def __init__(self, path):
            self._file = open(path, "wb")  # durable-handle: single-record
        def flush(self):
            self._file.flush()
            os.fsync(self._file.fileno())
        def append(self, head, body):
            self._file.write(head)
            self.flush()
            self._file.write(body)
            self.flush()
    """
    assert findings_for(src, SERVICE, "FL-DUR-TORN") == []
    broken = src.replace("            self.flush()\n"
                         "            self._file.write(body)",
                         "            self._file.write(body)")
    hits = findings_for(broken, SERVICE, "FL-DUR-TORN")
    assert len(hits) == 1 and "torn record" in hits[0].message


# -- project rule: FL-DUR-SEAM -----------------------------------------------


def _write_seam_tree(root, faults_body, service_body):
    pkg = root / "fluidframework_tpu"
    (pkg / "testing").mkdir(parents=True)
    (pkg / "service").mkdir()
    (pkg / "testing" / "faults.py").write_text(textwrap.dedent(faults_body))
    (pkg / "service" / "x.py").write_text(textwrap.dedent(service_body))


def test_dur_seam_positive(tmp_path):
    _write_seam_tree(tmp_path, """
        SITES = {
            "shard.kill": "kill a shard host",
            "oplog.lost": "drop an oplog append",
        }
        SCHEDULED_SITES = ("shard.kill", "client.stall")
    """, """
        def hurt(faults):
            faults.fire("shard.kill")
            faults.fire("proc.vanish")
    """)
    msgs = {f.message for f in analyze(tmp_path) if f.rule == "FL-DUR-SEAM"}
    assert any("'proc.vanish' is fired here but not registered" in m
               for m in msgs), msgs
    assert any("'oplog.lost' is armed nowhere" in m for m in msgs), msgs
    assert any("'client.stall' is not a SITES key" in m for m in msgs), msgs


def test_dur_seam_negative(tmp_path):
    _write_seam_tree(tmp_path, """
        SITES = {
            "shard.kill": "kill a shard host",
            "oplog.lost": "drop an oplog append",
        }
        SCHEDULED_SITES = ("shard.kill",)
    """, """
        def hurt(faults):
            faults.fire("oplog.lost")
            for site in ("shard.kill",):
                faults.due(site)
    """)
    assert [f for f in analyze(tmp_path) if f.rule == "FL-DUR-SEAM"] == []


# -- project rule: FL-DUR-GATE -----------------------------------------------


def _write_gate_tree(root, gates_body, service_body):
    pkg = root / "fluidframework_tpu" / "service"
    pkg.mkdir(parents=True)
    (pkg / "gates.py").write_text(textwrap.dedent(gates_body))
    (pkg / "x.py").write_text(textwrap.dedent(service_body))


def test_dur_gate_positive(tmp_path):
    _write_gate_tree(tmp_path, """
        GATES = {
            "Catchup.Cache": "on",
            "Catchup.Ghost": 1,
        }
    """, """
        def read(config):
            config.get_str("Catchup.Cache", "on")
            config.get_int("Server.Unknown", 1)
    """)
    msgs = {f.message for f in analyze(tmp_path) if f.rule == "FL-DUR-GATE"}
    assert any("'Server.Unknown' is read here but not registered" in m
               for m in msgs), msgs
    assert any("'Catchup.Ghost' is never read" in m for m in msgs), msgs


def test_dur_gate_negative(tmp_path):
    _write_gate_tree(tmp_path, """
        GATES = {
            "Catchup.Cache": "on",
            "Server.DrainRetryAfter": 0.5,
        }
    """, """
        def read(config):
            config.get_str("Catchup.Cache", "on")
            config.get_float("Server.DrainRetryAfter", 0.5)
    """)
    assert [f for f in analyze(tmp_path) if f.rule == "FL-DUR-GATE"] == []


# -- project rules: FL-ERR-CODE / FL-ERR-RAISE / FL-ERR-RETRY ------------------


def _write_err_tree(root, errors_body, service_body):
    pkg = root / "fluidframework_tpu"
    (pkg / "protocol").mkdir(parents=True)
    (pkg / "service").mkdir()
    (pkg / "protocol" / "errors.py").write_text(textwrap.dedent(errors_body))
    (pkg / "service" / "x.py").write_text(textwrap.dedent(service_body))


def test_err_code_positive(tmp_path):
    _write_err_tree(tmp_path, """
        WIRE_ERRORS = {
            "throttled": {"channel": "nack"},
            "epochMismatch": {"channel": "frame"},
            "ghostCode": {"channel": "frame"},
        }
        EXCEPTIONS = {}
    """, """
        def reply(err):
            if err.code == "mystery":
                return {"ok": False, "code": "freeLancer"}
            return {"ok": False, "code": "epochMismatch"}
    """)
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-ERR-CODE"}
    assert any("'freeLancer' is produced here but not registered" in m
               for m in msgs), msgs
    assert any("'mystery' is handled here but not registered" in m
               for m in msgs), msgs
    assert any("'ghostCode' is produced nowhere" in m for m in msgs), msgs
    assert any("'epochMismatch' is produced but never handled" in m
               for m in msgs), msgs


def test_err_code_negative(tmp_path):
    _write_err_tree(tmp_path, """
        WIRE_ERRORS = {
            "throttled": {"channel": "nack"},
            "epochMismatch": {"channel": "frame"},
        }
        EXCEPTIONS = {}
    """, """
        def reply(err):
            if err.code == "epochMismatch":
                return {"ok": False, "code": "epochMismatch"}
            return {"ok": False, "code": "throttled"}
    """)
    assert [f for f in analyze(tmp_path)
            if f.rule == "FL-ERR-CODE"] == []


def test_err_raise_positive(tmp_path):
    _write_err_tree(tmp_path, """
        WIRE_ERRORS = {
            "throttled": {"channel": "nack"},
            "epochMismatch": {"channel": "frame"},
        }
        EXCEPTIONS = {}
    """, """
        def pace():
            raise NackError("busy", code="fluxCapacitor")

        def fence():
            raise NackError("stale", code="epochMismatch")
    """)
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-ERR-RAISE"}
    assert any("free-string code 'fluxCapacitor'" in m for m in msgs), msgs
    assert any("'epochMismatch', a frame-channel code" in m
               for m in msgs), msgs


def test_err_raise_negative(tmp_path):
    _write_err_tree(tmp_path, """
        WIRE_ERRORS = {
            "throttled": {"channel": "nack"},
        }
        EXCEPTIONS = {}
    """, """
        def pace():
            raise NackError("busy", code="throttled")
    """)
    assert [f for f in analyze(tmp_path)
            if f.rule == "FL-ERR-RAISE"] == []


def test_err_retry_positive(tmp_path):
    _write_err_tree(tmp_path, """
        WIRE_ERRORS = {}
        EXCEPTIONS = {
            "RpcTransportError": {"retry": "transport"},
            "ConnectionLostError": {"retry": "reconnect",
                                    "parent": "RpcTransportError"},
        }
    """, """
        def call(policy, op):
            return policy.run(
                operation=op,
                retry_on=(RpcTransportError, OSError),
            )
    """)
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-ERR-RETRY"}
    assert any("reconnect-class exception 'ConnectionLostError'" in m
               and "absent from no_retry" in m for m in msgs), msgs


def test_err_retry_negative(tmp_path):
    _write_err_tree(tmp_path, """
        WIRE_ERRORS = {}
        EXCEPTIONS = {
            "RpcTransportError": {"retry": "transport"},
            "ConnectionLostError": {"retry": "reconnect",
                                    "parent": "RpcTransportError"},
        }
    """, """
        def call(policy, op):
            return policy.run(
                operation=op,
                retry_on=(RpcTransportError, OSError),
                no_retry=(ConnectionLostError,),
            )
    """)
    assert [f for f in analyze(tmp_path)
            if f.rule == "FL-ERR-RETRY"] == []


# -- fluidshape: FL-KERN-BLOCK behavior ---------------------------------------


def test_kern_block_annotation_accepts_unprovable_dim():
    src = """
    from jax.experimental import pallas as pl
    def _round_up(n, mult):
        return ((n + mult - 1) // mult) * mult
    def fold(x, Sp):
        return pl.BlockSpec((8, Sp), lambda d: (d, 0))  # block-rule: _round_up
    """
    assert findings_for(src, OPS, "FL-KERN-BLOCK") == []


def test_kern_block_annotation_typo_is_a_finding():
    # a typo'd '# block-rule:' must not silently exempt the dim
    src = """
    from jax.experimental import pallas as pl
    def _round_up(n, mult):
        return ((n + mult - 1) // mult) * mult
    def fold(x, Sp):
        return pl.BlockSpec((8, Sp), lambda d: (d, 0))  # block-rule: _round_upp
    """
    hits = findings_for(src, OPS, "FL-KERN-BLOCK")
    assert len(hits) == 2  # the bad annotation AND the unproven dim
    assert any("no recognized rounding helper" in f.message for f in hits)


def test_kern_block_proven_violation_fires_despite_annotation():
    # annotations excuse what the rule cannot prove, never what it can
    src = """
    from jax.experimental import pallas as pl
    def _round_up(n, mult):
        return ((n + mult - 1) // mult) * mult
    def fold(x):
        return pl.BlockSpec((8, 100), lambda d: (d, 0))  # block-rule: _round_up
    """
    hits = findings_for(src, OPS, "FL-KERN-BLOCK")
    assert len(hits) == 1 and "literal 100" in hits[0].message


def test_kern_block_tuple_helper_route_accepted():
    # the tuple-helper shape: dims unpacked from a tuple-returning wrapper
    # around the canonical round-up, consts aliased locally, grid algebra
    # over rounded names
    src = """
    import jax
    from jax.experimental import pallas as pl
    DOC_BLOCK = 8
    LANE = 128
    def _round_up(n, mult):
        return ((n + mult - 1) // mult) * mult
    def _padded_dims(D, S):
        return (_round_up(max(D, 1), DOC_BLOCK),
                _round_up(max(S, 1), LANE))
    def fold(kernel, x, D, S):
        Dp, Sp = _padded_dims(D, S)
        B = DOC_BLOCK
        row = pl.BlockSpec((B, Sp), lambda d: (d, 0))
        return pl.pallas_call(kernel, grid=(Dp // B,), in_specs=[row])
    """
    assert findings_for(src, OPS, "FL-KERN-BLOCK") == []


def test_kern_block_wrong_position_rounding_fires():
    # a dim rounded to the SUBLANE multiple used in the lane position is
    # a proven violation — 8 does not divide 128
    src = """
    from jax.experimental import pallas as pl
    DOC_BLOCK = 8
    LANE = 128
    def _round_up(n, mult):
        return ((n + mult - 1) // mult) * mult
    def _padded_dims(D, S):
        return (_round_up(max(D, 1), DOC_BLOCK),
                _round_up(max(S, 1), LANE))
    def fold(x, D, S):
        Dp, Sp = _padded_dims(D, S)
        return pl.BlockSpec((8, Dp), lambda d: (d, 0))
    """
    hits = findings_for(src, OPS, "FL-KERN-BLOCK")
    assert len(hits) == 1
    assert "rounded to multiples of 8, not of 128" in hits[0].message


def test_kern_block_is_interpret_mode_blind():
    # interpret=True accepts blocks Mosaic rejects — the r05 failure.
    # The rule must fire regardless of the interpret kwarg.
    src = """
    from jax.experimental import pallas as pl
    def fold(kernel, x, D):
        return pl.pallas_call(kernel, grid=(D // 8,), interpret=True)
    """
    hits = findings_for(src, OPS, "FL-KERN-BLOCK")
    assert len(hits) == 1 and "grid extent" in hits[0].message


# -- fluidshape: FL-KERN-NARROW behavior --------------------------------------


def test_kern_narrow_bound_annotation_accepted():
    src = """
    import numpy as np
    I16_LIMIT = 32766
    def pack(vals):
        return vals.astype(np.int16)  # bound: I16_LIMIT
    """
    assert findings_for(src, OPS, "FL-KERN-NARROW") == []


def test_kern_narrow_bound_annotation_typo_is_a_finding():
    src = """
    import numpy as np
    I16_LIMIT = 32766
    def pack(vals):
        return vals.astype(np.int16)  # bound: I16_LIMIT_TYPO
    """
    hits = findings_for(src, OPS, "FL-KERN-NARROW")
    assert len(hits) == 1
    assert "references no bound guard" in hits[0].message


def test_kern_narrow_dtype_compare_is_a_guard():
    # relayout of an ALREADY-narrow buffer narrows nothing
    src = """
    import numpy as np
    def relayout(buf):
        if buf.dtype != np.int16:
            return None
        return np.ascontiguousarray(buf, np.int16)
    """
    assert findings_for(src, OPS, "FL-KERN-NARROW") == []


def test_kern_narrow_accumulation_on_narrow_lanes_fires():
    src = """
    import numpy as np
    def total(vals):
        packed = vals.astype(np.int16)
        return packed.sum()
    """
    hits = findings_for(src, OPS, "FL-KERN-NARROW")
    assert any("accumulating op on narrow lanes 'packed'" in f.message
               for f in hits)


def test_kern_narrow_iinfo_is_a_guard():
    src = """
    import numpy as np
    def pack(vals):
        info = np.iinfo(np.int16)
        ok = vals.max() <= info.max
        return vals.astype(np.int16) if ok else vals
    """
    assert findings_for(src, OPS, "FL-KERN-NARROW") == []


# -- fluidshape: FL-KERN-BUCKET behavior --------------------------------------


def test_kern_bucket_annotation_accepted():
    src = """
    import jax
    @jax.jit
    def _fold(x, n):
        return x[:n]
    def run(x, docs):
        return _fold(x, len(docs))  # bucketed-by: next_bucket
    """
    assert findings_for(src, OPS, "FL-KERN-BUCKET") == []


def test_kern_bucket_annotation_typo_is_a_finding():
    src = """
    import jax
    @jax.jit
    def _fold(x, n):
        return x[:n]
    def run(x, docs):
        return _fold(x, len(docs))  # bucketed-by: next_bucket_typo
    """
    hits = findings_for(src, OPS, "FL-KERN-BUCKET")
    assert len(hits) == 2  # the bad annotation AND the unrouted shape
    assert any("no recognized bucket or rounding helper" in f.message
               for f in hits)


def test_kern_bucket_taint_flows_through_names():
    # D = len(docs) is dirty; rebinding through the ladder cleans it
    src = """
    import jax
    from .interning import next_bucket
    @jax.jit
    def _fold(x, n):
        return x[:n]
    def dirty(x, docs):
        D = len(docs)
        return _fold(x, D)
    def clean(x, docs):
        D = next_bucket(len(docs))
        return _fold(x, D)
    """
    hits = findings_for(src, OPS, "FL-KERN-BUCKET")
    assert len(hits) == 1 and "in dirty()" in hits[0].message


def test_kern_bucket_jit_factory_calls_checked():
    # the lru-cached factory idiom: factory(...)(args) reaches a jit
    src = """
    import jax
    import functools
    @functools.lru_cache(maxsize=8)
    def _fold_fn(static):
        return jax.jit(lambda x, n: x[:n])
    def run(x, docs):
        return _fold_fn(True)(x, len(docs))
    """
    hits = findings_for(src, OPS, "FL-KERN-BUCKET")
    assert len(hits) == 1 and "_fold_fn" in hits[0].message


# -- fluidshape: FL-KERN-PAD behavior -----------------------------------------


def test_kern_pad_masked_by_annotation_accepted():
    src = """
    import jax.numpy as jnp
    def digest(x, mask):
        plane = jnp.pad(x, ((0, 3),))
        return plane.sum()  # masked-by: mask
    """
    assert findings_for(src, OPS, "FL-KERN-PAD") == []


def test_kern_pad_masked_by_typo_is_a_finding():
    src = """
    import jax.numpy as jnp
    def digest(x, mask):
        plane = jnp.pad(x, ((0, 3),))
        return plane.sum()  # masked-by: maskk
    """
    hits = findings_for(src, OPS, "FL-KERN-PAD")
    assert len(hits) == 2  # the bad annotation AND the unmasked reduce
    assert any("no name" in f.message for f in hits)


def test_kern_pad_mask_reassignment_clears():
    src = """
    import jax.numpy as jnp
    def digest(x, mask):
        plane = jnp.pad(x, ((0, 3),))
        plane = jnp.where(mask, plane, 0)
        return plane.sum()
    """
    assert findings_for(src, OPS, "FL-KERN-PAD") == []


def test_kern_pad_inline_chain_fires():
    src = """
    import jax.numpy as jnp
    def digest(x):
        return jnp.pad(x, ((0, 3),)).sum()
    """
    hits = findings_for(src, OPS, "FL-KERN-PAD")
    assert len(hits) == 1 and "reaches reduction 'sum'" in hits[0].message


# -- project rule: FL-KERN-FAMILY ---------------------------------------------


def _write_family_tree(root, pipeline_body, shard_body):
    ops = root / "fluidframework_tpu" / "ops"
    par = root / "fluidframework_tpu" / "parallel"
    ops.mkdir(parents=True)
    par.mkdir(parents=True)
    (ops / "family.py").write_text(textwrap.dedent("""
        from dataclasses import dataclass
        @dataclass(frozen=True)
        class KernelFamily:
            name: str
            pack: object
            dispatch: object
            make_pad: object = None
            pad_token: object = None
            dispatch_sharded: object = None
    """))
    (ops / "pipeline.py").write_text(textwrap.dedent(pipeline_body))
    (par / "shard.py").write_text(textwrap.dedent(shard_body))


def test_kern_family_positive(tmp_path):
    _write_family_tree(tmp_path, """
        from .family import KernelFamily
        STAGE_KEYS = ("pack", "upload", "dispatch", "device_wait",
                      "download", "extract")
        def seed_stage(stage):
            return stage
        FAM = KernelFamily(
            name="mt", pack=object(),
            make_pad=None, pad_token=object(),
            dispatch_sharded=object(), chunk_tag=object(),
        )
    """, """
        def replay_sharded(stage):
            return stage
    """)
    msgs = {f.message for f in analyze(tmp_path)
            if f.rule == "FL-KERN-FAMILY"}
    assert any("omits descriptor hook 'dispatch'" in m for m in msgs), msgs
    assert any("unknown hook 'chunk_tag'" in m for m in msgs), msgs
    assert any("mesh hook 'make_pad' is None" in m for m in msgs), msgs
    assert any("diverges from the canonical stage schema" in m
               for m in msgs), msgs
    assert any("mesh twin never seeds" in m for m in msgs), msgs


def test_kern_family_negative(tmp_path):
    _write_family_tree(tmp_path, """
        from .family import KernelFamily
        STAGE_KEYS = ("pack", "upload", "dispatch", "device_wait",
                      "download", "extract", "fallback")
        def seed_stage(stage):
            return stage
        FAM = KernelFamily(
            name="mt", pack=object(), dispatch=object(),
            make_pad=object(), pad_token=object(),
            dispatch_sharded=object(),
        )
    """, """
        from ..ops.pipeline import seed_stage
        def replay_sharded(stage):
            return seed_stage(stage)
    """)
    assert [f for f in analyze(tmp_path)
            if f.rule == "FL-KERN-FAMILY"] == []


# -- registry meta-coverage ----------------------------------------------------


def test_registry_fully_self_tested():
    """Every registered rule must carry at least one positive (fires)
    and one negative (stays quiet) self-test: module rules through a
    MODULE_RULE_FIXTURES pair, project rules through named
    test_<slug>_positive/negative functions.  A future rule landing
    without tests fails HERE, not silently in production."""
    from tools.fluidlint import all_rules
    from tools.fluidlint.core import ProjectRule

    rules = all_rules()
    module_ids = {n for n, r in rules.items()
                  if not isinstance(r, ProjectRule)}
    missing = sorted(module_ids - set(MODULE_RULE_FIXTURES))
    assert not missing, (
        f"module rules without a (positive, negative) fixture pair in "
        f"MODULE_RULE_FIXTURES: {missing}")
    unknown = sorted(set(MODULE_RULE_FIXTURES) - module_ids)
    assert not unknown, f"fixtures for unregistered rules: {unknown}"
    for rule_id in sorted(set(rules) - module_ids):
        slug = rule_id.lower().replace("fl-", "", 1).replace("-", "_")
        for suffix in ("positive", "negative"):
            assert f"test_{slug}_{suffix}" in globals(), (
                f"{rule_id}: project rule needs a test_{slug}_{suffix}")


# -- baseline rule-id hygiene --------------------------------------------------


def test_rule_hygiene_flags_unregistered_rule_id():
    from tools.fluidlint import baseline_rule_hygiene
    problems = baseline_rule_hygiene([
        {"rule": "FL-GONE-RULE", "path": "x.py", "message": "m",
         "reason": "r"}])
    assert problems and "not registered" in problems[0]
    assert baseline_rule_hygiene([
        {"rule": "FL-DET-CLOCK", "path": "x.py", "message": "m",
         "reason": "r"}]) == []


def test_check_baseline_flags_unregistered_rule_id(tmp_path, capsys):
    from tools.fluidlint.cli import main
    _clock_violation_tree(tmp_path)
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "FL-GONE-RULE",
         "path": "fluidframework_tpu/loader/bad.py",
         "message": "m", "reason": "reviewed"}]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "--check-baseline"]) == 1
    assert "not registered" in capsys.readouterr().out


def test_unregistered_rule_entry_fails_even_under_rules_filter(tmp_path):
    # --rules filtering ignores entries of UNSELECTED rules, but an
    # UNREGISTERED rule id is dead weight on every run: the hygiene
    # check consults the full, unfiltered registry and baseline.
    from tools.fluidlint.cli import main
    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "ok.py").write_text("X = 1\n")
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "FL-GONE-RULE",
         "path": "fluidframework_tpu/loader/ok.py",
         "message": "m", "reason": "reviewed"}]}))
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "--rules", "FL-RACE"]) == 1
