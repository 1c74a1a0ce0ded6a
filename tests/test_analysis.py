"""gltlint rule tests: each rule fires on a violating fixture and stays
silent on the clean twin; the CLI gate passes over glt_tpu itself.

Fixtures are minimal but idiomatic — the same import spellings the real
tree uses (``import jax.numpy as jnp``, ``from functools import partial``)
so alias resolution is exercised, not bypassed.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from glt_tpu.analysis import Severity, analyze_source
from glt_tpu.analysis.cli import analyze_project
from glt_tpu.analysis.rules import RULES
from glt_tpu.analysis.symbols import Project
from glt_tpu.analysis.visitor import ModuleInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def findings_for(src, rule=None):
    out = analyze_source(textwrap.dedent(src), "fixture.py")
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


def make_project(sources):
    """A Project from ``{dotted_module_name: source}`` (no filesystem)."""
    mods = [
        ModuleInfo(name.replace(".", "/") + ".py", textwrap.dedent(src),
                   module_name=name)
        for name, src in sources.items()
    ]
    return Project(mods)


def project_findings(sources, rule=None):
    out = analyze_project(make_project(sources))
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ---------------------------------------------------------------------------
# GLT001 host-sync-in-jit
# ---------------------------------------------------------------------------

class TestHostSyncInJit:
    def test_positive_np_asarray_on_traced(self):
        src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x) + 1
        """
        hits = findings_for(src, "host-sync-in-jit")
        assert len(hits) == 1
        assert hits[0].severity is Severity.ERROR
        assert "np" not in hits[0].rule  # sanity: rule name, not module

    def test_positive_item_inside_wrapped_method(self):
        src = """
        import jax

        class S:
            def __init__(self):
                self._fn = jax.jit(self._impl)

            def _impl(self, ids):
                return ids.sum().item()
        """
        assert len(findings_for(src, "host-sync-in-jit")) == 1

    def test_positive_int_on_traced_param(self):
        src = """
        import jax

        @jax.jit
        def f(x):
            n = int(x)
            return n
        """
        assert len(findings_for(src, "host-sync-in-jit")) == 1

    def test_negative_host_side_and_static(self):
        src = """
        import jax
        import numpy as np

        def host_stage(ids):
            return np.asarray(ids)          # not a jit context

        @jax.jit
        def f(x):
            b = int(x.shape[0])             # .shape is static under jit
            return x * b

        @jax.jit
        def g(x, n):
            return x + np.float32(1.0)      # constant, no traced operand
        """
        assert findings_for(src, "host-sync-in-jit") == []

    def test_negative_static_argnames_excluded(self):
        src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return x * int(n)
        """
        assert findings_for(src, "host-sync-in-jit") == []

    def test_transitive_helper_with_static_args_clean(self):
        # the bounded_remote_cap shape: helper called from jit with
        # Python config values only
        src = """
        import jax

        def cap(width, load):
            return int(round(load * width))

        @jax.jit
        def f(x):
            c = cap(4, 2.0)
            return x[:c]
        """
        assert findings_for(src, "host-sync-in-jit") == []

    def test_transitive_helper_with_traced_arg_fires(self):
        src = """
        import jax
        import numpy as np

        def helper(v):
            return np.asarray(v)

        @jax.jit
        def f(x):
            return helper(x * 2)
        """
        assert len(findings_for(src, "host-sync-in-jit")) == 1


# ---------------------------------------------------------------------------
# GLT002 prng-key-reuse
# ---------------------------------------------------------------------------

class TestPrngKeyReuse:
    def test_positive_double_draw(self):
        src = """
        import jax

        def sample(key):
            a = jax.random.uniform(key, (4,))
            b = jax.random.normal(key, (4,))
            return a + b
        """
        hits = findings_for(src, "prng-key-reuse")
        assert len(hits) == 1
        assert "key" in hits[0].message

    def test_positive_reuse_after_local_key(self):
        src = """
        import jax

        def sample(x):
            k = jax.random.PRNGKey(0)
            a = jax.random.uniform(k, (4,))
            b = jax.random.uniform(k, (4,))
            return a + b
        """
        assert len(findings_for(src, "prng-key-reuse")) == 1

    def test_negative_split_and_fold_in(self):
        src = """
        import jax

        def sample(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.uniform(k1, (4,))
            b = jax.random.normal(k2, (4,))
            for i in range(3):
                ki = jax.random.fold_in(key, i)   # deriving is fine
                b = b + jax.random.uniform(ki, (4,))
            return a + b
        """
        assert findings_for(src, "prng-key-reuse") == []

    def test_negative_branches_use_once_each(self):
        src = """
        import jax

        def sample(key, flag):
            if flag:
                return jax.random.uniform(key, (4,))
            else:
                return jax.random.normal(key, (4,))
        """
        assert findings_for(src, "prng-key-reuse") == []

    def test_negative_reassignment_resets(self):
        src = """
        import jax

        def sample(key):
            a = jax.random.uniform(key, (4,))
            key = jax.random.fold_in(key, 1)
            b = jax.random.uniform(key, (4,))
            return a + b
        """
        assert findings_for(src, "prng-key-reuse") == []


# ---------------------------------------------------------------------------
# GLT003 recompile-hazard
# ---------------------------------------------------------------------------

class TestRecompileHazard:
    def test_positive_closure_over_scalar(self):
        src = """
        import jax

        def build(x):
            n = x.shape[0]
            fn = jax.jit(lambda a: a * n)
            return fn
        """
        hits = findings_for(src, "recompile-hazard")
        assert len(hits) == 1
        assert "'n'" in hits[0].message

    def test_positive_nested_def_capture(self):
        src = """
        import jax

        def build(batches):
            width = len(batches)

            def body(a):
                return a + width

            return jax.jit(body)
        """
        assert len(findings_for(src, "recompile-hazard")) == 1

    def test_negative_static_argnums(self):
        src = """
        import jax

        def build(x):
            n = x.shape[0]
            fn = jax.jit(lambda a, m: a * m, static_argnums=(1,))
            return fn, n
        """
        assert findings_for(src, "recompile-hazard") == []

    def test_negative_no_scalar_capture(self):
        src = """
        import jax
        import jax.numpy as jnp

        def build(rows):
            table = jnp.asarray(rows, jnp.float32)   # array capture: fine
            return jax.jit(lambda ids: table[ids])
        """
        assert findings_for(src, "recompile-hazard") == []

    def test_suppression_comment(self):
        src = """
        import jax

        def build(x):
            n = x.shape[0]
            fn = jax.jit(lambda a: a * n)  # gltlint: disable=recompile-hazard -- cached per n
            return fn
        """
        assert findings_for(src, "recompile-hazard") == []


# ---------------------------------------------------------------------------
# GLT004 int64-id-truncation
# ---------------------------------------------------------------------------

class TestInt64IdTruncation:
    def test_positive_astype_flow(self):
        src = """
        import numpy as np
        import jax.numpy as jnp

        def load(ids):
            ids64 = np.asarray(ids).astype(np.int64)
            return jnp.asarray(ids64)
        """
        hits = findings_for(src, "int64-id-truncation")
        assert len(hits) == 1
        assert hits[0].severity is Severity.ERROR

    def test_positive_dtype_kwarg_source(self):
        src = """
        import numpy as np
        import jax.numpy as jnp

        def load(n):
            eids = np.arange(n, dtype=np.int64)
            return jnp.array(eids)
        """
        assert len(findings_for(src, "int64-id-truncation")) == 1

    def test_negative_explicit_dtype(self):
        src = """
        import numpy as np
        import jax.numpy as jnp

        def load(ids):
            ids64 = np.asarray(ids).astype(np.int64)
            a = jnp.asarray(ids64, jnp.int32)        # positional dtype
            b = jnp.asarray(ids64, dtype=jnp.int32)  # keyword dtype
            mask = ids64 >= 0                        # bool, not ids
            return a, b, jnp.asarray(mask)
        """
        assert findings_for(src, "int64-id-truncation") == []


# ---------------------------------------------------------------------------
# GLT005 nondeterministic-default-rng
# ---------------------------------------------------------------------------

class TestNondeterministicDefaultRng:
    def test_positive_unseeded(self):
        src = """
        import numpy as np

        def shuffle(ids):
            return np.random.default_rng().permutation(ids)
        """
        hits = findings_for(src, "nondeterministic-default-rng")
        assert len(hits) == 1
        assert hits[0].severity is Severity.WARNING

    def test_positive_explicit_none(self):
        src = """
        import numpy as np

        rng = np.random.default_rng(None)
        """
        assert len(findings_for(src, "nondeterministic-default-rng")) == 1

    def test_positive_fresh_generator_per_call(self):
        # the dist_dataset.py:76 bug: a fresh default_rng(seed) drawn
        # inline inside a function whose seed is a parameter replays the
        # identical permutation on every call (epoch)
        src = """
        import numpy as np

        def split(ids, seed=0):
            return np.random.default_rng(seed).permutation(ids)
        """
        hits = findings_for(src, "nondeterministic-default-rng")
        assert len(hits) == 1
        assert "replays" in hits[0].message

    def test_negative_seeded_one_shot_and_threaded(self):
        src = """
        import numpy as np

        FIXTURE = np.random.default_rng(0).permutation(16)   # one-shot

        def split(ids, rng: np.random.Generator):
            return rng.permutation(ids)                      # threaded

        def per_step(ids, step):
            # per-call-varying seed: a deliberate stream
            return np.random.default_rng(step * 7 + 1).permutation(ids)
        """
        assert findings_for(src, "nondeterministic-default-rng") == []


# ---------------------------------------------------------------------------
# GLT006 shadowed-jit-donation
# ---------------------------------------------------------------------------

class TestShadowedJitDonation:
    def test_positive_use_after_donation(self):
        src = """
        import jax

        step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))

        def train(state, batch):
            out = step(state, batch)
            return out + state.sum()     # state's buffer is gone
        """
        hits = findings_for(src, "shadowed-jit-donation")
        assert len(hits) == 1
        assert "'state'" in hits[0].message

    def test_positive_decorated_donation(self):
        src = """
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(1,))
        def step(state, scratch):
            return state + scratch

        def loop(state, scratch):
            state = step(state, scratch)
            return state, scratch.shape  # read after donate
        """
        assert len(findings_for(src, "shadowed-jit-donation")) == 1

    def test_negative_reassigned_from_result(self):
        src = """
        import jax

        step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))

        def train(state, batches):
            for b in batches:
                state = step(state, b)   # donated then rebound
            return state
        """
        assert findings_for(src, "shadowed-jit-donation") == []

    def test_negative_undonated_args_free(self):
        src = """
        import jax

        step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))

        def train(state, batch):
            out = step(state, batch)
            return out + batch.sum()     # batch was not donated
        """
        assert findings_for(src, "shadowed-jit-donation") == []


# ---------------------------------------------------------------------------
# GLT007 unbounded-blocking-get
# ---------------------------------------------------------------------------

class TestUnboundedBlockingGet:
    def test_positive_bare_queue_get(self):
        src = """
        import queue

        def consume(q):
            item = q.get()          # blocks forever if producer died
            return item
        """
        hits = findings_for(src, "unbounded-blocking-get")
        assert len(hits) == 1
        assert ".get()" in hits[0].message

    def test_positive_bare_thread_join(self):
        src = """
        import threading

        def stop(worker):
            worker.stop_flag = True
            worker.thread.join()    # thread may be wedged on a queue
        """
        assert len(findings_for(src, "unbounded-blocking-get")) == 1

    def test_negative_timeout_kwarg(self):
        src = """
        def consume(q):
            return q.get(timeout=0.5)

        def stop(t):
            t.join(5)
        """
        assert findings_for(src, "unbounded-blocking-get") == []

    def test_negative_liveness_recheck_in_scope(self):
        src = """
        import queue

        def consume(q, thread):
            while True:
                try:
                    return q.get(timeout=0.5)
                except queue.Empty:
                    if not thread.is_alive():
                        raise RuntimeError("producer died")
        """
        assert findings_for(src, "unbounded-blocking-get") == []

    def test_negative_argful_get_join_are_not_blocking(self):
        src = """
        import os

        def lookup(d, parts):
            root = os.environ.get("ROOT")
            return d.get(root), ",".join(parts)
        """
        assert findings_for(src, "unbounded-blocking-get") == []

    def test_suppression_with_justification(self):
        src = """
        def worker_loop(tasks):
            while True:
                # Parent owns this worker's lifetime; wait is bounded.
                # gltlint: disable-next=unbounded-blocking-get
                cmd = tasks.get()
                if cmd is None:
                    return
        """
        assert findings_for(src, "unbounded-blocking-get") == []


# ---------------------------------------------------------------------------
# GLT010 span-in-traced-code
# ---------------------------------------------------------------------------

class TestSpanInTracedCode:
    def test_positive_span_and_counter_in_jit(self):
        src = """
        import jax
        from glt_tpu.obs.trace import span
        from glt_tpu.obs import metrics

        _M_STEPS = metrics.counter("glt.x.steps", "steps")

        @jax.jit
        def step(x):
            with span("step"):            # vanishes under trace
                _M_STEPS.inc()            # counts compilations, not calls
                return x + 1
        """
        hits = findings_for(src, "span-in-traced-code")
        assert len(hits) == 2
        assert any("span" in h.message for h in hits)
        assert any(".inc()" in h.message for h in hits)

    def test_positive_chained_factory_in_jit(self):
        src = """
        import jax
        from glt_tpu import obs

        @jax.jit
        def step(x):
            obs.metrics.counter("glt.y").inc()
            return x * 2
        """
        # both the factory call and the chained .inc() resolve into obs;
        # at least one finding must land on the statement
        assert len(findings_for(src, "span-in-traced-code")) >= 1

    def test_positive_nested_def_inside_jit(self):
        src = """
        import jax
        from glt_tpu.obs.trace import span

        @jax.jit
        def outer(x):
            def body(y):
                with span("inner"):
                    return y + 1
            return body(x)
        """
        assert len(findings_for(src, "span-in-traced-code")) == 1

    def test_negative_host_loop_instrumentation(self):
        src = """
        import jax
        from glt_tpu.obs.trace import span
        from glt_tpu.obs import metrics

        _M_STEPS = metrics.counter("glt.x.steps", "steps")

        @jax.jit
        def step(x):
            return x + 1

        def epoch(batches):
            for b in batches:             # host loop: the right boundary
                with span("step") as sp:
                    out = step(b)
                    sp.fence(out)
                _M_STEPS.inc()
        """
        assert findings_for(src, "span-in-traced-code") == []

    def test_negative_at_set_is_not_an_obs_call(self):
        src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def scatter(x, i):
            y = x.at[i].set(0.0)          # jnp functional update, not obs
            c = {}
            c.update(n=1)
            return y
        """
        assert findings_for(src, "span-in-traced-code") == []

    def test_negative_non_obs_inc_receiver(self):
        src = """
        import jax

        @jax.jit
        def step(counter, x):
            counter.inc()                 # unknown receiver: not flagged
            return x
        """
        assert findings_for(src, "span-in-traced-code") == []

    def test_negative_device_scopes_belong_in_traced_code(self):
        src = """
        import jax
        from glt_tpu.obs.scopes import scoped

        @jax.jit
        def step(x):
            @scoped("glt.model.agg")
            def agg(y):
                return y.sum()
            with jax.named_scope("glt.model.dense"):
                return agg(x) + 1
        """
        assert findings_for(src, "span-in-traced-code") == []

    def test_suppression_with_justification(self):
        src = """
        import jax
        from glt_tpu.obs.trace import span

        @jax.jit
        def step(x):
            # Fixture exercising trace-time-only span (documented).
            # gltlint: disable-next=span-in-traced-code
            with span("trace-time-only"):
                return x + 1
        """
        assert findings_for(src, "span-in-traced-code") == []


# ---------------------------------------------------------------------------
# GLT011 non-atomic-state-publish
# ---------------------------------------------------------------------------

class TestNonAtomicStatePublish:
    def test_positive_direct_final_path_write(self):
        src = """
        import json

        def save_manifest(path, obj):
            with open(path, "w") as fh:
                json.dump(obj, fh)
        """
        fs = findings_for(src, "non-atomic-state-publish")
        assert len(fs) == 1 and "os.replace" in fs[0].message

    def test_positive_mode_keyword_and_append(self):
        src = """
        def log_artifact(report_path, line):
            with open(report_path, mode="a") as fh:
                fh.write(line)
        """
        assert len(findings_for(src, "non-atomic-state-publish")) == 1

    def test_positive_module_level_write(self):
        src = """
        import json
        with open("artifacts/results.json", "w") as fh:
            json.dump({}, fh)
        """
        assert len(findings_for(src, "non-atomic-state-publish")) == 1

    def test_negative_tmp_plus_replace(self):
        # The glt_tpu.ckpt.store discipline: private tmp, one rename.
        src = """
        import json
        import os

        def publish(path, obj):
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(obj, fh)
            os.replace(tmp, path)
        """
        assert findings_for(src, "non-atomic-state-publish") == []

    def test_negative_tmp_named_path_without_replace(self):
        # A visibly process-private scratch file needs no publish step.
        src = """
        def scratch(obj):
            with open("/tmp/debug-dump.txt", "w") as fh:
                fh.write(str(obj))
        """
        assert findings_for(src, "non-atomic-state-publish") == []

    def test_negative_read_mode_untouched(self):
        src = """
        import json

        def load(path):
            with open(path) as fh:
                return json.load(fh)

        def load_binary(path):
            with open(path, "rb") as fh:
                return fh.read()
        """
        assert findings_for(src, "non-atomic-state-publish") == []

    def test_negative_shutil_move_publish(self):
        src = """
        import shutil
        import tempfile

        def publish(path, text):
            fd, tmp = tempfile.mkstemp()
            with open(tmp, "w") as fh:
                fh.write(text)
            shutil.move(tmp, path)
        """
        assert findings_for(src, "non-atomic-state-publish") == []


# ---------------------------------------------------------------------------
# GLT012 unbounded-queue-put
# ---------------------------------------------------------------------------

class TestUnboundedQueuePut:
    def test_positive_bare_queue(self):
        src = """
        import queue

        def make_buffer():
            return queue.Queue()
        """
        fs = findings_for(src, "unbounded-queue-put")
        assert len(fs) == 1 and "maxsize" in fs[0].message

    def test_positive_from_import_and_zero_maxsize(self):
        src = """
        from queue import Queue

        buf = Queue(maxsize=0)
        lifo = Queue(0)
        """
        assert len(findings_for(src, "unbounded-queue-put")) == 2

    def test_positive_simplequeue(self):
        src = """
        import queue

        q = queue.SimpleQueue()
        """
        fs = findings_for(src, "unbounded-queue-put")
        assert len(fs) == 1 and "cannot be bounded" in fs[0].message

    def test_negative_bounded_spellings(self):
        src = """
        import queue
        from queue import Queue

        a = queue.Queue(maxsize=8)
        b = Queue(16)
        c = queue.LifoQueue(maxsize=4)
        d = queue.Queue(maxsize=capacity)   # dynamic bound: trusted
        """
        assert findings_for(src, "unbounded-queue-put") == []

    def test_negative_multiprocessing_out_of_scope(self):
        src = """
        import multiprocessing as mp

        def make_task_queue(ctx):
            return ctx.Queue()

        q = mp.Queue()
        """
        assert findings_for(src, "unbounded-queue-put") == []

    def test_suppression(self):
        src = """
        import queue

        q = queue.Queue()  # gltlint: disable=unbounded-queue-put
        """
        assert findings_for(src, "unbounded-queue-put") == []


# ---------------------------------------------------------------------------
# GLT013 dispatch-in-epoch-loop
# ---------------------------------------------------------------------------

class TestDispatchInEpochLoop:
    def test_positive_device_get_in_loop(self):
        src = """
        import jax

        def run_scanned_epoch(step, state, blocks):
            losses = []
            for blk in blocks:
                state, loss = step(state, blk)
                losses.append(float(jax.device_get(loss)))
            return state, losses
        """
        fs = findings_for(src, "dispatch-in-epoch-loop")
        assert len(fs) == 2          # device_get + float coercion
        assert any("every batch" in f.message for f in fs)

    def test_positive_asarray_and_item(self):
        src = """
        import numpy as np

        def _run_epoch(step, state, batches):
            out = []
            for b in batches:
                state, loss = step(state, b)
                out.append(np.asarray(loss))
                print(loss.item())
            return out
        """
        fs = findings_for(src, "dispatch-in-epoch-loop")
        assert len(fs) == 2
        assert any(".item()" in f.message for f in fs)

    def test_positive_block_until_ready_in_while(self):
        src = """
        import jax

        def run_pipelined_epoch(step, state, it):
            while True:
                b = next(it, None)
                if b is None:
                    break
                state, loss = step(state, b)
                jax.block_until_ready(loss)
            return state
        """
        fs = findings_for(src, "dispatch-in-epoch-loop")
        assert len(fs) == 1

    def test_negative_fetch_after_loop(self):
        src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def run_scanned_epoch(step, state, blocks):
            losses = []
            for blk in blocks:
                state, loss = step(state, blk)
                losses.append(loss)
            # ONE concat + ONE host fetch at the epoch boundary: the
            # contract the rule enforces.
            return state, np.asarray(jax.device_get(
                jnp.concatenate(losses)))
        """
        assert findings_for(src, "dispatch-in-epoch-loop") == []

    def test_negative_non_epoch_function(self):
        src = """
        import numpy as np

        def collect_all(step, state, batches):
            out = []
            for b in batches:
                state, loss = step(state, b)
                out.append(np.asarray(loss))
            return out
        """
        assert findings_for(src, "dispatch-in-epoch-loop") == []

    def test_transitive_helper_sync(self):
        fs = project_findings({
            "pkg.stats": """
                import numpy as np

                def publish_stats(loss):
                    return float(np.asarray(loss))
            """,
            "pkg.driver": """
                from pkg.stats import publish_stats

                def run_scanned_epoch(step, state, blocks):
                    for blk in blocks:
                        state, loss = step(state, blk)
                        publish_stats(loss)
                    return state
            """,
        }, "dispatch-in-epoch-loop")
        assert len(fs) == 1
        assert "publish_stats" in fs[0].message
        assert "hidden per-batch round trip" in fs[0].message

    def test_suppression(self):
        src = """
        import jax

        def run_scanned_epoch(step, state, blocks, on_block=None):
            for i, blk in enumerate(blocks):
                state, loss = step(state, blk)
                if on_block is not None:
                    # checkpoint hook: the sync is the contract
                    # gltlint: disable-next=dispatch-in-epoch-loop
                    jax.block_until_ready(state)
                    on_block(state, i)
            return state
        """
        assert findings_for(src, "dispatch-in-epoch-loop") == []


# ---------------------------------------------------------------------------
# GLT014 blocking-io-in-epoch-loop
# ---------------------------------------------------------------------------

class TestBlockingIOInEpochLoop:
    def test_positive_np_load_in_loop(self):
        src = """
        import numpy as np

        def run_scanned_epoch(step, state, paths):
            for p in paths:
                rows = np.load(p)
                state = step(state, rows)
            return state
        """
        fs = findings_for(src, "blocking-io-in-epoch-loop")
        assert len(fs) == 1
        assert "stage ahead" in fs[0].message

    def test_positive_memmap_slice_in_loop(self):
        # The constructor is hoisted above the loop; the slice INSIDE
        # the loop is the per-batch page fault.
        src = """
        import numpy as np

        def run_epoch(step, state, batches, path):
            mm = np.memmap(path, dtype=np.float32, mode="r")
            for b in batches:
                state = step(state, mm[b])
            return state
        """
        fs = findings_for(src, "blocking-io-in-epoch-loop")
        assert len(fs) == 1
        assert "page-fault" in fs[0].message

    def test_positive_file_read_in_loop(self):
        src = """
        def run_stream_epoch(step, state, fh, n):
            while n > 0:
                raw = fh.read(4096)
                state = step(state, raw)
                n -= 1
            return state
        """
        fs = findings_for(src, "blocking-io-in-epoch-loop")
        assert len(fs) == 1
        assert ".read()" in fs[0].message

    def test_negative_non_epoch_function(self):
        # Staging helpers read disk by design — only epoch drivers are
        # in scope.
        src = """
        import numpy as np

        def _stage(store, ids, out):
            for lo in range(0, len(ids), 1024):
                out[lo:lo + 1024] = np.load(store)[ids[lo:lo + 1024]]
        """
        assert findings_for(src, "blocking-io-in-epoch-loop") == []

    def test_negative_read_outside_loop(self):
        src = """
        import numpy as np

        def run_scanned_epoch(step, state, path, batches):
            rows = np.load(path)      # once, at the epoch boundary
            for b in batches:
                state = step(state, rows[b])
            return state
        """
        assert findings_for(src, "blocking-io-in-epoch-loop") == []

    def test_transitive_helper_disk_read(self):
        fs = project_findings({
            "pkg.store": """
                import numpy as np

                def load_rows(path, ids):
                    return np.load(path)[ids]
            """,
            "pkg.driver": """
                from pkg.store import load_rows

                def run_scanned_epoch(step, state, path, batches):
                    for b in batches:
                        state = step(state, load_rows(path, b))
                    return state
            """,
        }, "blocking-io-in-epoch-loop")
        assert len(fs) == 1
        assert "load_rows" in fs[0].message
        assert "disk read" in fs[0].message

    def test_suppression(self):
        src = """
        import numpy as np

        def run_epoch(step, state, path, batches):
            for b in batches:
                # degraded fallback: a failed stage left these rows on
                # disk, and correctness beats latency here
                # gltlint: disable-next=blocking-io-in-epoch-loop
                rows = np.load(path)
                state = step(state, rows[b])
            return state
        """
        assert findings_for(src, "blocking-io-in-epoch-loop") == []


# ---------------------------------------------------------------------------
# GLT015 wall-clock-duration
# ---------------------------------------------------------------------------

class TestWallClockDuration:
    def test_positive_stopwatch_from_time_time(self):
        src = """
        import time

        def measure(fn):
            t0 = time.time()
            fn()
            return time.time() - t0
        """
        fs = findings_for(src, "wall-clock-duration")
        assert len(fs) == 1
        assert fs[0].code == "GLT015"
        assert "time.monotonic" in fs[0].message

    def test_positive_both_sides_named(self):
        src = """
        import time

        def measure(fn):
            t0 = time.time()
            fn()
            t1 = time.time()
            return t1 - t0
        """
        assert len(findings_for(src, "wall-clock-duration")) == 1

    def test_negative_timestamp_comparison(self):
        # Comparing a wall reading against a FILE timestamp is the
        # legitimate use (ckpt freshness checks): only wall-minus-wall
        # is a stopwatch.
        src = """
        import os
        import time

        def age_seconds(path):
            return time.time() - os.path.getmtime(path)
        """
        assert findings_for(src, "wall-clock-duration") == []

    def test_negative_perf_counter(self):
        src = """
        import time

        def measure(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0
        """
        assert findings_for(src, "wall-clock-duration") == []

    def test_suppression(self):
        src = """
        import time

        def heartbeat_age(last_beat_wall):
            # cross-process ages compare wall stamps by design
            # gltlint: disable-next=wall-clock-duration
            return time.time() - last_beat_wall
        """
        # last_beat_wall is a parameter, not a wall read — already
        # clean; the suppressed direct form must be clean too:
        assert findings_for(src, "wall-clock-duration") == []
        src2 = """
        import time

        def measure(fn):
            t0 = time.time()
            fn()
            # gltlint: disable-next=wall-clock-duration
            return time.time() - t0
        """
        assert findings_for(src2, "wall-clock-duration") == []


# ---------------------------------------------------------------------------
# GLT016 unbalanced-profiler-capture
# ---------------------------------------------------------------------------

class TestUnbalancedProfilerCapture:
    def test_positive_bare_start(self):
        src = """
        import jax

        def profile_epoch(run, d):
            jax.profiler.start_trace(d)
            run()
            jax.profiler.stop_trace()
        """
        fs = findings_for(src, "unbalanced-profiler-capture")
        assert len(fs) == 1
        assert fs[0].code == "GLT016"
        assert "finally" in fs[0].message

    def test_positive_stop_only_in_except(self):
        # stop in an except handler doesn't run on the success path's
        # early return, and isn't the balanced shape.
        src = """
        import jax

        def profile_epoch(run, d):
            jax.profiler.start_trace(d)
            try:
                run()
            except ValueError:
                jax.profiler.stop_trace()
        """
        assert len(findings_for(src, "unbalanced-profiler-capture")) == 1

    def test_negative_start_then_try_finally(self):
        # The contextmanager idiom (obs/profiler.py capture()): start
        # BEFORE the try, stop in its finally.
        src = """
        import jax

        def profile_epoch(run, d):
            jax.profiler.start_trace(d)
            try:
                run()
            finally:
                jax.profiler.stop_trace()
        """
        assert findings_for(src, "unbalanced-profiler-capture") == []

    def test_negative_start_inside_try(self):
        src = """
        import jax

        def profile_epoch(run, d):
            try:
                jax.profiler.start_trace(d)
                run()
            finally:
                jax.profiler.stop_trace()
        """
        assert findings_for(src, "unbalanced-profiler-capture") == []

    def test_negative_alias_import(self):
        src = """
        from jax import profiler as _jprof

        def profile_epoch(run, d):
            _jprof.start_trace(d)
            try:
                run()
            finally:
                _jprof.stop_trace()
        """
        assert findings_for(src, "unbalanced-profiler-capture") == []

    def test_positive_alias_unbalanced(self):
        src = """
        from jax import profiler as _jprof

        def profile_epoch(run, d):
            _jprof.start_trace(d)
            run()
        """
        assert len(findings_for(src, "unbalanced-profiler-capture")) == 1

    def test_positive_start_server(self):
        src = """
        import jax

        def serve(port):
            jax.profiler.start_server(port)
            work()
        """
        fs = findings_for(src, "unbalanced-profiler-capture")
        assert len(fs) == 1
        assert "stop_server" in fs[0].message

    def test_negative_capture_ctx(self):
        # The blessed wrapper: no raw start/stop at all.
        src = """
        from glt_tpu.obs import profiler as obs_profiler

        def profile_epoch(run, d):
            with obs_profiler.capture(d, millis=50):
                run()
        """
        assert findings_for(src, "unbalanced-profiler-capture") == []

    def test_nested_scopes_independent(self):
        # The balanced inner function must not excuse the module-level
        # bare start.
        src = """
        import jax

        jax.profiler.start_trace("/tmp/t")

        def ok(run, d):
            jax.profiler.start_trace(d)
            try:
                run()
            finally:
                jax.profiler.stop_trace()
        """
        assert len(findings_for(src, "unbalanced-profiler-capture")) == 1

    def test_suppression(self):
        src = """
        import jax

        def repl_start(d):
            # interactive notebook seam: the user stops it by hand
            # gltlint: disable-next=unbalanced-profiler-capture
            jax.profiler.start_trace(d)
        """
        assert findings_for(src, "unbalanced-profiler-capture") == []


# ---------------------------------------------------------------------------
# the project engine: symbols, call graph, effects
# ---------------------------------------------------------------------------

class TestSymbolsAndCallGraph:
    def test_import_aliasing_cross_module(self):
        # `from x import y as z` must land on the one definition
        sources = {
            "pkg.helpers": """
                import numpy as np

                def to_host(v):
                    return np.asarray(v)
            """,
            "pkg.main": """
                import jax
                from pkg.helpers import to_host as th

                @jax.jit
                def f(x):
                    return th(x * 2)
            """,
        }
        hits = project_findings(sources, "host-sync-in-jit")
        assert len(hits) == 1
        assert hits[0].path == "pkg/main.py"
        assert "np" not in hits[0].rule

    def test_reexport_through_package_init(self):
        mods = [
            ModuleInfo("pkg/__init__.py",
                       "from .helpers import to_host\n",
                       module_name="pkg"),
            ModuleInfo("pkg/helpers.py", textwrap.dedent("""
                import numpy as np

                def to_host(v):
                    return np.asarray(v)
            """), module_name="pkg.helpers"),
            ModuleInfo("pkg/main.py", textwrap.dedent("""
                import jax
                from pkg import to_host

                @jax.jit
                def f(x):
                    return to_host(x)
            """), module_name="pkg.main"),
        ]
        project = Project(mods)
        hits = [f for f in analyze_project(project)
                if f.rule == "host-sync-in-jit"]
        assert len(hits) == 1 and hits[0].path == "pkg/main.py"

    def test_relative_import_resolution(self):
        mods = [
            ModuleInfo("pkg/helpers.py", textwrap.dedent("""
                import numpy as np

                def to_host(v):
                    return np.asarray(v)
            """), module_name="pkg.helpers"),
            ModuleInfo("pkg/main.py", textwrap.dedent("""
                import jax
                from .helpers import to_host

                @jax.jit
                def f(x):
                    return to_host(x)
            """), module_name="pkg.main"),
        ]
        hits = [f for f in analyze_project(Project(mods))
                if f.rule == "host-sync-in-jit"]
        assert len(hits) == 1

    def test_callgraph_cycle_terminates_and_propagates(self):
        # mutual recursion: effect computation must neither hang nor miss
        # the blocking effect inside the cycle
        project = make_project({"pkg.cyc": """
            import time

            def a(n):
                if n > 0:
                    b(n - 1)
                time.sleep(0.1)

            def b(n):
                a(n)
        """})
        eng = project.effects
        for fid in ("pkg.cyc.a", "pkg.cyc.b"):
            assert eng.summaries[fid].blocking, fid

    def test_callgraph_bounded_depth_cutoff(self):
        chain = "\n\n".join(
            [f"def f{i}(x):\n    return f{i + 1}(x)" for i in range(5)]
            + ["def f5(x):\n    return x"])
        project = make_project({"pkg.chain": chain})
        graph = project.effects.graph
        depths = graph.reachable("pkg.chain.f0", max_depth=2)
        assert depths == {"pkg.chain.f0": 0, "pkg.chain.f1": 1,
                          "pkg.chain.f2": 2}
        assert len(graph.reachable("pkg.chain.f0")) == 6

    def test_effect_chain_depth_cutoff(self):
        # a blocking effect buried deeper than MAX_CHAIN_DEPTH calls is
        # cut off rather than propagated forever
        from glt_tpu.analysis.effects import MAX_CHAIN_DEPTH
        n = MAX_CHAIN_DEPTH + 3
        parts = ["import time", "def g0():\n    time.sleep(1)"]
        for i in range(1, n):
            parts.append(f"def g{i}():\n    g{i - 1}()")
        project = make_project({"pkg.deep": "\n\n".join(parts)})
        eng = project.effects
        assert eng.summaries["pkg.deep.g0"].blocking
        assert eng.summaries[f"pkg.deep.g{MAX_CHAIN_DEPTH - 1}"].blocking
        assert not eng.summaries[f"pkg.deep.g{n - 1}"].blocking

    def test_method_resolution_via_constructor_type(self):
        project = make_project({"pkg.svc": """
            import socket
            import threading

            class Conn:
                def __init__(self):
                    self.sock = socket.socket()

                def roundtrip(self):
                    return self.sock.recv(64)

            class Owner:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.conn = Conn()

                def locked_io(self):
                    with self._lock:
                        return self.conn.roundtrip()
        """})
        hits = [f for f in analyze_project(project)
                if f.rule == "blocking-call-while-holding-lock"]
        assert len(hits) == 1
        assert "roundtrip" in hits[0].message


# ---------------------------------------------------------------------------
# GLT001/GLT002 transitive (cross-module) upgrades
# ---------------------------------------------------------------------------

class TestHostSyncTransitive:
    HELPERS = """
        import numpy as np

        def to_host(v):
            return np.asarray(v)

        def cap(width, load):
            return int(round(load * width))
    """

    def test_positive_traced_arg_into_cross_module_sync(self):
        hits = project_findings({
            "pkg.helpers": self.HELPERS,
            "pkg.main": """
                import jax
                from pkg.helpers import to_host

                @jax.jit
                def f(x):
                    return to_host(x * 2)
            """,
        }, "host-sync-in-jit")
        assert len(hits) == 1
        assert hits[0].path == "pkg/main.py"
        assert "to_host" in hits[0].message
        assert "helpers.py" in hits[0].message   # the chain names the sink

    def test_negative_static_config_args_stay_clean(self):
        hits = project_findings({
            "pkg.helpers": self.HELPERS,
            "pkg.main": """
                import jax
                from pkg.helpers import cap, to_host

                def host_stage(ids):
                    return to_host(ids)        # not a jit context

                @jax.jit
                def f(x):
                    c = cap(4, 2.0)            # Python config only
                    return x[:c]
            """,
        }, "host-sync-in-jit")
        assert hits == []

    def test_positive_two_level_chain(self):
        # jit -> mid (other module) -> sink (third module)
        hits = project_findings({
            "pkg.sink": """
                import numpy as np

                def materialize(arr):
                    return np.asarray(arr)
            """,
            "pkg.mid": """
                from pkg.sink import materialize

                def relay(v):
                    return materialize(v)
            """,
            "pkg.main": """
                import jax
                from pkg.mid import relay

                @jax.jit
                def f(x):
                    return relay(x)
            """,
        }, "host-sync-in-jit")
        assert len(hits) == 1 and hits[0].path == "pkg/main.py"

    def test_cross_module_jit_wrap_marks_entry_point(self):
        # jax.jit(imported_fn): the wrap is in main, the body (and the
        # finding) in the helper module
        hits = project_findings({
            "pkg.step": """
                import numpy as np

                def step(x):
                    return np.asarray(x) + 1
            """,
            "pkg.main": """
                import jax
                from pkg.step import step

                train = jax.jit(step)
            """,
        }, "host-sync-in-jit")
        assert len(hits) == 1 and hits[0].path == "pkg/step.py"


class TestPrngKeyReuseTransitive:
    KEYS = """
        import jax

        def draw(k, shape):
            return jax.random.uniform(k, shape)

        def derive(k, n):
            return jax.random.fold_in(k, n)
    """

    def test_positive_cross_module_consuming_helper(self):
        hits = project_findings({
            "pkg.keys": self.KEYS,
            "pkg.main": """
                from pkg.keys import draw

                def sample(key):
                    a = draw(key, (4,))
                    b = draw(key, (4,))
                    return a + b
            """,
        }, "prng-key-reuse")
        assert len(hits) == 1
        assert "'key'" in hits[0].message

    def test_negative_resolved_deriving_helper_not_consuming(self):
        # the precision upgrade: a helper that only fold_ins its key is
        # as safe as jax.random.fold_in itself (the flow-light rule used
        # to count any call as consumption)
        hits = project_findings({
            "pkg.keys": self.KEYS,
            "pkg.main": """
                import jax
                from pkg.keys import derive

                def sample(key):
                    a = jax.random.uniform(derive(key, 1), (4,))
                    b = jax.random.uniform(derive(key, 2), (4,))
                    return a + b
            """,
        }, "prng-key-reuse")
        assert hits == []

    def test_positive_two_level_consumption(self):
        hits = project_findings({
            "pkg.keys": self.KEYS,
            "pkg.mid": """
                from pkg.keys import draw

                def noise(k):
                    return draw(k, (8,))
            """,
            "pkg.main": """
                from pkg.mid import noise

                def sample(key):
                    return noise(key) + noise(key)
            """,
        }, "prng-key-reuse")
        assert len(hits) == 1


# ---------------------------------------------------------------------------
# GLT008 lock-order-inversion
# ---------------------------------------------------------------------------

class TestLockOrderInversion:
    def test_positive_nested_with_inversion(self):
        src = """
        import threading

        class S:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()

            def f(self):
                with self.a:
                    with self.b:
                        pass

            def g(self):
                with self.b:
                    with self.a:
                        pass
        """
        hits = findings_for(src, "lock-order-inversion")
        assert len(hits) == 1
        assert hits[0].severity is Severity.ERROR
        assert "S.a" in hits[0].message and "S.b" in hits[0].message

    def test_positive_transitive_cross_module_inversion(self):
        hits = project_findings({
            "pkg.locks": """
                import threading

                LOCK_A = threading.Lock()
                LOCK_B = threading.Lock()

                def take_b():
                    with LOCK_B:
                        pass

                def path1():
                    with LOCK_A:
                        take_b()
            """,
            "pkg.other": """
                from pkg.locks import LOCK_A, LOCK_B

                def take_a():
                    with LOCK_A:
                        pass

                def path2():
                    with LOCK_B:
                        take_a()
            """,
        }, "lock-order-inversion")
        assert len(hits) == 1        # one report per inverted pair
        assert "LOCK_A" in hits[0].message and "LOCK_B" in hits[0].message

    def test_negative_consistent_order(self):
        src = """
        import threading

        class S:
            def __init__(self):
                self.a = threading.Lock()
                self.b = threading.Lock()

            def f(self):
                with self.a:
                    with self.b:
                        pass

            def g(self):
                with self.a:
                    with self.b:
                        pass
        """
        assert findings_for(src, "lock-order-inversion") == []

    def test_negative_same_lock_reentry_not_reported(self):
        src = """
        import threading

        class S:
            def __init__(self):
                self.a = threading.Lock()

            def f(self):
                with self.a:
                    pass

            def g(self):
                with self.a:
                    pass
        """
        assert findings_for(src, "lock-order-inversion") == []


# ---------------------------------------------------------------------------
# GLT009 blocking-call-while-holding-lock
# ---------------------------------------------------------------------------

class TestBlockingUnderLock:
    def test_positive_socket_recv_under_lock(self):
        src = """
        import socket
        import threading

        class Conn:
            def __init__(self):
                self._lock = threading.Lock()
                self.sock = socket.socket()

            def fetch(self):
                with self._lock:
                    return self.sock.recv(4096)
        """
        hits = findings_for(src, "blocking-call-while-holding-lock")
        assert len(hits) == 1
        assert "recv" in hits[0].message and "_lock" in hits[0].message

    def test_positive_blocking_helper_called_under_lock(self):
        # the effect is one call deep: the lock holder calls a helper
        # whose summary says it may block on a zero-arg get
        src = """
        import threading

        def drain(q):
            return q.get()

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def fetch(self, q):
                with self._lock:
                    return drain(q)
        """
        hits = findings_for(src, "blocking-call-while-holding-lock")
        assert len(hits) == 1
        assert "drain" in hits[0].message

    def test_positive_sleep_under_module_lock(self):
        src = """
        import threading
        import time

        _LOCK = threading.Lock()

        def slow():
            with _LOCK:
                time.sleep(1.0)
        """
        assert len(findings_for(
            src, "blocking-call-while-holding-lock")) == 1

    def test_negative_blocking_outside_critical_section(self):
        src = """
        import socket
        import threading

        class Conn:
            def __init__(self):
                self._lock = threading.Lock()
                self.sock = socket.socket()

            def fetch(self):
                with self._lock:
                    n = 4096
                return self.sock.recv(n)
        """
        assert findings_for(src, "blocking-call-while-holding-lock") == []

    def test_negative_liveness_poll_helper_exempt(self):
        # the GLT007 timeout-and-recheck pattern (bounded_get) is not a
        # blocking source, even when invoked under a lock
        src = """
        import queue
        import threading

        def bounded(q, thread):
            while True:
                try:
                    return q.get(timeout=0.5)
                except queue.Empty:
                    if not thread.is_alive():
                        raise RuntimeError("source died")

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def fetch(self, q, thread):
                with self._lock:
                    return bounded(q, thread)
        """
        assert findings_for(src, "blocking-call-while-holding-lock") == []

    def test_negative_condition_wait_monitor_pattern(self):
        src = """
        import threading

        class C:
            def __init__(self):
                self._cv = threading.Condition()

            def wait_ready(self):
                with self._cv:
                    self._cv.wait()
        """
        assert findings_for(src, "blocking-call-while-holding-lock") == []

    def test_one_finding_per_scope_and_lock(self):
        src = """
        import socket
        import threading
        import time

        class Conn:
            def __init__(self):
                self._lock = threading.Lock()
                self.sock = socket.socket()

            def fetch(self):
                with self._lock:
                    time.sleep(0.1)
                    return self.sock.recv(4096)
        """
        assert len(findings_for(
            src, "blocking-call-while-holding-lock")) == 1

    def test_suppression_with_justification(self):
        src = """
        import socket
        import threading

        class Conn:
            def __init__(self):
                self._lock = threading.Lock()
                self.sock = socket.socket()

            def fetch(self):
                with self._lock:
                    # Request-response stream; interrupt() is the escape.
                    # gltlint: disable-next=blocking-call-while-holding-lock
                    return self.sock.recv(4096)
        """
        assert findings_for(src, "blocking-call-while-holding-lock") == []


# ---------------------------------------------------------------------------
# suppression / report plumbing
# ---------------------------------------------------------------------------

class TestSuppression:
    SRC = """
    import numpy as np

    a = np.random.default_rng()
    """

    def test_line_disable_by_name_and_code(self):
        for tag in ("nondeterministic-default-rng", "GLT005", "all"):
            src = self.SRC.replace(
                "default_rng()", f"default_rng()  # gltlint: disable={tag}")
            assert findings_for(src) == []

    def test_disable_next_line(self):
        src = """
        import numpy as np

        # gltlint: disable-next=GLT005 -- entropy wanted here
        a = np.random.default_rng()
        """
        assert findings_for(src) == []

    def test_disable_file(self):
        src = """
        # gltlint: disable-file=nondeterministic-default-rng
        import numpy as np

        a = np.random.default_rng()
        b = np.random.default_rng()
        """
        assert findings_for(src) == []

    def test_unsuppressed_still_fires(self):
        assert len(findings_for(self.SRC)) == 1

    def test_parse_error_is_a_finding(self):
        bad = "def f(:\n    pass\n"
        out = analyze_source(bad, "broken.py")
        assert len(out) == 1 and out[0].rule == "parse-error"


# ---------------------------------------------------------------------------
# GLT017 vmem-budget-exceeded
# ---------------------------------------------------------------------------

# Indented to match the fixture bodies it is concatenated with, so
# textwrap.dedent (inside findings_for) strips a uniform prefix.
PALLAS_HEADER = """
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
"""


class TestVmemBudgetExceeded:
    def test_overflowing_scratch_fires(self):
        src = PALLAS_HEADER + """
        def kern(o_ref, buf):
            o_ref[...] = buf[0]

        def run(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((65536, 128), jnp.float32)],
            )(x)
        """
        out = findings_for(src, "vmem-budget-exceeded")
        assert len(out) == 1
        assert out[0].severity is Severity.ERROR
        assert "32.0MB" in out[0].message and "16.0MB" in out[0].message

    def test_small_kernel_clean(self):
        src = PALLAS_HEADER + """
        def kern(o_ref, buf):
            o_ref[...] = buf[0]

        def run(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)],
            )(x)
        """
        assert findings_for(src, "vmem-budget-exceeded") == []

    def test_constant_resolution_dict_and_default(self):
        """Dims resolve through a module constant, a function default,
        and the module-level VMEM_MODEL_DOMAIN sweep dict; the finding
        names the overflowing candidate point."""
        src = PALLAS_HEADER + """
        TILE = 256
        VMEM_MODEL_DOMAIN = {"d": (128, 4096)}

        def kern(o_ref, buf):
            o_ref[...] = buf[0]

        def run(x, ring=32):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                scratch_shapes=[
                    pltpu.VMEM((ring, TILE, d), jnp.float32)],
            )(x)
        """
        out = findings_for(src, "vmem-budget-exceeded")
        assert len(out) == 1
        assert "d=4096" in out[0].message
        assert "ring=32" in out[0].message
        # every candidate point under budget -> clean
        clean = src.replace('"d": (128, 4096)', '"d": (128,)')
        clean = clean.replace("ring=32", "ring=4")
        assert findings_for(clean, "vmem-budget-exceeded") == []

    def test_unmodelable_dim_is_an_error(self):
        """A dim the model cannot bound is itself a finding — the
        accounting must stay total, and the fix (declare the domain) is
        named in the message."""
        src = PALLAS_HEADER + """
        def kern(o_ref, buf):
            o_ref[...] = buf[0]

        def run(x, width):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((8, width), jnp.float32)],
            )(x)
        """
        out = findings_for(src, "vmem-budget-exceeded")
        assert len(out) == 1
        assert "VMEM_MODEL_DOMAIN" in out[0].message
        assert "width" in out[0].message

    def test_gridded_blocks_count_double_buffered(self):
        """With a grid, in/out blocks are pipeline double-buffered: a
        5MB block models as 10MB and clears a 16MB budget only without
        the x2."""
        src = PALLAS_HEADER + """
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((2048, 1024),
                                       lambda c: (c, 0))],
                out_specs=pl.BlockSpec((2048, 1024), lambda c: (c, 0)),
                out_shape=jax.ShapeDtypeStruct((8192, 1024), jnp.float32),
            )(x)
        """
        out = findings_for(src, "vmem-budget-exceeded")
        assert len(out) == 1
        assert "2x" in out[0].message

    def test_budget_resolves_from_tpu_limits_module(self):
        """The budget is the project's own ops/tpu_limits.py constant,
        not a hardcoded analyzer copy."""
        limits = "VMEM_BYTES = 1024\nLANE = 128\nSUBLANE_F32 = 8\n"
        kern = PALLAS_HEADER + """
        from . import tpu_limits

        def kern(o_ref, buf):
            o_ref[...] = buf[0]

        def run(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            )(x)
        """
        out = project_findings(
            {"pkg.ops.tpu_limits": limits, "pkg.ops.kern": kern},
            "vmem-budget-exceeded")
        assert len(out) == 1            # 4KB out vs the 1KB budget
        assert "1.0KB" in out[0].message


# ---------------------------------------------------------------------------
# GLT018 unbalanced-dma-ring
# ---------------------------------------------------------------------------

class TestUnbalancedDmaRing:
    POS = PALLAS_HEADER + """
        def make_kernel(nbuf):
            def kernel(idx_ref, x_ref, o_ref, buf, sems):
                def dma(j):
                    return pltpu.make_async_copy(
                        x_ref.at[pl.ds(j, 1)], buf.at[pl.ds(j, 1)],
                        sems.at[lax.rem(j, nbuf)])

                def body(j, c):
                    @pl.when(idx_ref[j] >= 0)
                    def _():
                        dma(j).start()

                    dma(j).wait()
                    return c

                lax.fori_loop(0, 8, body, None)
            return kernel
    """

    def test_start_guard_without_matching_wait_guard(self):
        out = findings_for(self.POS, "unbalanced-dma-ring")
        assert len(out) == 1
        assert "idx_ref[j] >= 0" in out[0].message
        assert "never-signaled" in out[0].message

    def test_symmetric_guards_clean(self):
        src = self.POS.replace(
            "dma(j).wait()",
            "@pl.when(idx_ref[j] >= 0)\n"
            "                    def _w():\n"
            "                        dma(j).wait()")
        assert findings_for(src, "unbalanced-dma-ring") == []

    def test_ring_control_guards_are_exempt(self):
        """The fill prologue legitimately guards start with `j + nbuf <
        n` and nothing else — loop-index arithmetic is ring control, not
        a row predicate, and must not fire."""
        src = PALLAS_HEADER + """
        def make_kernel(nbuf, n):
            def kernel(x_ref, o_ref, buf, sems):
                def dma(j):
                    return pltpu.make_async_copy(
                        x_ref.at[pl.ds(j, 1)], buf.at[pl.ds(j, 1)],
                        sems.at[lax.rem(j, nbuf)])

                for k in range(nbuf):
                    @pl.when(k < n)
                    def _():
                        dma(k).start()

                def body(j, c):
                    dma(j).wait()

                    @pl.when(j + nbuf < n)
                    def _():
                        dma(j + nbuf).start()

                    return c

                lax.fori_loop(0, n, body, None)
            return kernel
        """
        assert findings_for(src, "unbalanced-dma-ring") == []

    def test_start_without_any_wait(self):
        src = PALLAS_HEADER + """
        def make_kernel(nbuf):
            def kernel(x_ref, o_ref, buf, sems):
                def dma(j):
                    return pltpu.make_async_copy(
                        x_ref.at[pl.ds(j, 1)], buf.at[pl.ds(j, 1)],
                        sems.at[j])

                dma(0).start()
                o_ref[...] = buf[...]
            return kernel
        """
        out = findings_for(src, "unbalanced-dma-ring")
        assert len(out) == 1
        assert "never awaited" in out[0].message


# ---------------------------------------------------------------------------
# GLT019 unaligned-tile-shape
# ---------------------------------------------------------------------------

class TestUnalignedTileShape:
    def test_lane_violation_fires(self):
        src = PALLAS_HEADER + """
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 100), lambda c: (c, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda c: (c, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            )(x)
        """
        out = findings_for(src, "unaligned-tile-shape")
        assert len(out) == 1
        assert "128-lane" in out[0].message

    def test_bf16_sublane_floor(self):
        """bf16 packs two values per sublane row: the floor is 16, so an
        (8, 128) bf16 scratch fires while the same f32 shape is clean."""
        src = PALLAS_HEADER + """
        def kern(o_ref, buf):
            o_ref[...] = buf[...]

        def run(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)],
            )(x)
        """
        out = findings_for(src, "unaligned-tile-shape")
        assert len(out) == 1
        assert "16-sublane floor for bfloat16" in out[0].message
        clean = src.replace("jnp.bfloat16", "jnp.float32")
        assert findings_for(clean, "unaligned-tile-shape") == []


# ---------------------------------------------------------------------------
# GLT020 divergent-collective
# ---------------------------------------------------------------------------

class TestDivergentCollective:
    def test_cond_on_axis_index_with_collective(self):
        src = """
        from jax import lax

        def body(x):
            r = lax.axis_index("shard")
            return lax.cond(r > 0,
                            lambda v: lax.psum(v, "shard"),
                            lambda v: v, x)
        """
        out = findings_for(src, "divergent-collective")
        assert len(out) == 1
        assert "'r'" in out[0].message
        assert "lax.axis_index" in out[0].message    # dependence chain
        assert "deadlock" in out[0].message

    def test_taint_propagates_through_assignments(self):
        src = """
        from jax import lax

        def body(x):
            me = lax.axis_index("shard")
            is_leader = me == 0
            if is_leader:
                x = lax.all_to_all(x, "shard", 0, 0)
            return x
        """
        out = findings_for(src, "divergent-collective")
        assert len(out) == 1
        assert "'is_leader'" in out[0].message

    def test_psum_launders_taint(self):
        """The dist_train skip-step pattern: a predicate reduced with
        psum is uniform across shards and must not fire."""
        src = """
        import jax.numpy as jnp
        from jax import lax

        def body(seeds, state):
            me = lax.axis_index("shard")
            nvalid = lax.psum(jnp.sum((seeds >= 0) + me * 0), "shard")
            return lax.cond(nvalid > 0,
                            lambda s: lax.pmean(s, "shard"),
                            lambda s: s, state)
        """
        assert findings_for(src, "divergent-collective") == []

    def test_divergent_branch_without_collective_clean(self):
        src = """
        from jax import lax

        def body(x):
            r = lax.axis_index("shard")
            return lax.cond(r > 0, lambda v: v + 1, lambda v: v, x)
        """
        assert findings_for(src, "divergent-collective") == []


# ---------------------------------------------------------------------------
# GLT021 unknown-axis-name
# ---------------------------------------------------------------------------

class TestUnknownAxisName:
    def test_stale_axis_string_fires(self):
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def run(xs):
            mesh = Mesh(np.array(jax.devices()), ("data",))

            def body(x):
                return jax.lax.psum(x, "shard")

            return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"))(xs)
        """
        out = findings_for(src, "unknown-axis-name")
        assert len(out) == 1
        assert "'shard'" in out[0].message
        assert "'data'" in out[0].message

    def test_partition_spec_axis_checked(self):
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def run(xs):
            mesh = Mesh(np.array(jax.devices()), ("data",))

            def body(x):
                return jax.lax.psum(x, "data")

            return jax.shard_map(body, mesh=mesh, in_specs=P("model"),
                                 out_specs=P("data"))(xs)
        """
        out = findings_for(src, "unknown-axis-name")
        assert len(out) == 1
        assert "PartitionSpec" in out[0].message

    def test_parametric_mesh_stays_quiet(self):
        """multihost.global_mesh builds axes from a parameter — an open
        mesh produces no findings whatever the body names."""
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def global_mesh(axis_name="shard"):
            return Mesh(np.array(jax.devices()), (axis_name,))

        def run(xs):
            mesh = global_mesh()

            def body(x):
                return jax.lax.psum(x, "anything")

            return jax.shard_map(body, mesh=mesh, in_specs=P("shard"),
                                 out_specs=P("shard"))(xs)
        """
        assert findings_for(src, "unknown-axis-name") == []

    def test_matching_axes_clean(self):
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def run(xs):
            mesh = Mesh(np.array(jax.devices()), ("host", "chip"))

            def body(x):
                x = jax.lax.psum(x, "host")
                return jax.lax.all_gather(x, "chip")

            return jax.shard_map(body, mesh=mesh, in_specs=P("host"),
                                 out_specs=P("host"))(xs)
        """
        assert findings_for(src, "unknown-axis-name") == []

    def test_stale_flat_axis_on_2d_mesh_fires(self):
        """ISSUE 17 fixture: a body migrated to the 2-D (host, chip)
        mesh but still carrying the 1-D era's "shard" axis string is
        exactly the bug hierarchical routing introduces — the collective
        compiles against no axis and GLT021 must name both the stale
        string and the real axes."""
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def run(xs):
            mesh = Mesh(np.array(jax.devices()).reshape(2, -1),
                        ("host", "chip"))

            def body(x):
                x = jax.lax.all_to_all(x, "chip", 0, 0)
                return jax.lax.psum(x, "shard")

            return jax.shard_map(body, mesh=mesh,
                                 in_specs=P(("host", "chip")),
                                 out_specs=P(("host", "chip")))(xs)
        """
        out = findings_for(src, "unknown-axis-name")
        assert len(out) == 1
        assert "'shard'" in out[0].message
        assert "'host'" in out[0].message and "'chip'" in out[0].message


    def test_hier_exchange_on_2d_mesh_clean(self):
        """The sanctioned hierarchical pattern — intra-host all_to_all
        over the ICI axis, dedup, cross-host all_to_all over the DCN
        axis, tuple specs over both axes — produces no findings."""
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def run(xs):
            mesh = Mesh(np.array(jax.devices()).reshape(2, -1),
                        ("host", "chip"))

            def body(x):
                x = jax.lax.all_to_all(x, "chip", 0, 0)
                x = jax.lax.all_to_all(x, "host", 0, 0)
                return jax.lax.psum(x, ("host", "chip"))

            return jax.shard_map(body, mesh=mesh,
                                 in_specs=P(("host", "chip")),
                                 out_specs=P(("host", "chip")))(xs)
        """
        assert findings_for(src, "unknown-axis-name") == []

    def test_literal_forwarded_into_helper(self):
        """One transitive step: a literal axis string passed into a
        module function that forwards it to a collective."""
        src = """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        def reduce_all(x, axis_name):
            return jax.lax.psum(x, axis_name)

        def run(xs):
            mesh = Mesh(np.array(jax.devices()), ("data",))

            def body(x):
                return reduce_all(x, "stale")

            return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"))(xs)
        """
        out = findings_for(src, "unknown-axis-name")
        assert len(out) == 1
        assert "reduce_all" in out[0].message


class TestLossyDtypeNarrowing:
    """GLT022: narrowing .astype casts outside store/quant.py."""

    def test_narrow_casts_fire(self):
        src = """
        import numpy as np
        import jax.numpy as jnp
        import ml_dtypes

        def stage(rows):
            a = rows.astype(np.float16)
            b = rows.astype(jnp.bfloat16)
            c = rows.astype(ml_dtypes.bfloat16)
            d = rows.astype("int8")
            e = rows.astype(np.dtype("uint8"))
            return a, b, c, d, e
        """
        out = findings_for(src, "lossy-dtype-narrowing")
        assert len(out) == 5
        assert all("store/quant.py" in f.message for f in out)
        assert "numpy.float16" in out[0].message

    def test_widening_and_id_casts_clean(self):
        src = """
        import numpy as np
        import jax.numpy as jnp

        def stage(rows, ids):
            a = rows.astype(np.float32)        # widening / identity
            b = rows.astype(jnp.float64)
            c = ids.astype(np.int32)           # GLT004's territory
            d = rows.astype(rows.dtype)        # dynamic target
            e = rows.astype(a.dtype)
            return a, b, c, d, e
        """
        assert findings_for(src, "lossy-dtype-narrowing") == []

    def test_quant_module_exempt(self):
        """The codec module is the one place narrowing is legal — its
        casts carry manifest metadata and the bounded-error contract."""
        src = textwrap.dedent("""
            import numpy as np

            def encode(rows):
                return rows.astype(np.int8)
        """)
        from glt_tpu.analysis import analyze_source
        hits = [f for f in analyze_source(src, "glt_tpu/store/quant.py")
                if f.rule == "lossy-dtype-narrowing"]
        assert hits == []
        # same source under any other path fires
        hits = [f for f in analyze_source(src, "glt_tpu/store/disk.py")
                if f.rule == "lossy-dtype-narrowing"]
        assert len(hits) == 1

    def test_suppression_comment(self):
        src = """
        import numpy as np

        def stage(rows):
            return rows.astype(np.float16)  # gltlint: disable=GLT022
        """
        assert findings_for(src, "lossy-dtype-narrowing") == []

    def test_tree_is_clean(self):
        """No narrowing casts outside quant.py anywhere in glt_tpu —
        the ISSUE-18 baseline stays empty."""
        proc = _run_cli("glt_tpu", "--rule=GLT022")
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestUnjitteredRetryLoop:
    """GLT023: constant-duration sleeps in network retry loops."""

    def test_constant_sleep_in_retry_loop_fires(self):
        src = """
        import socket
        import time

        def fetch(conn):
            while True:
                try:
                    return conn.request()
                except (ConnectionResetError, socket.timeout):
                    time.sleep(0.5)
        """
        out = findings_for(src, "unjittered-retry-loop")
        assert len(out) == 1
        assert "jittered exponential backoff" in out[0].message

    def test_constant_wait_and_arithmetic_fire(self):
        src = """
        import time

        def fetch(ev, conn):
            for _ in range(5):
                try:
                    return conn.request()
                except EOFError:
                    ev.wait(2 * 0.25)

        def fetch2(conn):
            while True:
                try:
                    return conn.request()
                except OSError:
                    time.sleep(1 + 0.5)
        """
        assert len(findings_for(src, "unjittered-retry-loop")) == 2

    def test_jittered_and_computed_sleeps_clean(self):
        src = """
        import time

        def fetch(conn, rng):
            attempt = 0
            while True:
                try:
                    return conn.request()
                except OSError:
                    attempt += 1
                    time.sleep(min(0.5, 0.05 * 2 ** attempt)
                               * (0.5 + 0.5 * rng.random()))

        def fetch2(conn, backoff):
            while True:
                try:
                    return conn.request()
                except ConnectionError:
                    time.sleep(backoff)
        """
        assert findings_for(src, "unjittered-retry-loop") == []

    def test_non_network_loops_clean(self):
        """Heartbeat/poll loops pace themselves — catching bare
        Exception (or nothing) is not retrying a peer."""
        src = """
        import time

        def heartbeat(stop, probe):
            while not stop.is_set():
                try:
                    probe()
                except Exception:
                    pass
                stop.wait(1.0)

        def spin(work):
            for item in work:
                time.sleep(0.01)

        def key_retry(fn):
            while True:
                try:
                    return fn()
                except KeyError:
                    time.sleep(0.1)
        """
        assert findings_for(src, "unjittered-retry-loop") == []

    def test_suppression_comment(self):
        src = """
        import time

        def fetch(conn):
            while True:
                try:
                    return conn.request()
                except OSError:
                    time.sleep(0.5)  # gltlint: disable=GLT023
        """
        assert findings_for(src, "unjittered-retry-loop") == []

    def test_tree_is_clean(self):
        """Every retry loop in the tree paces with jittered backoff —
        the ISSUE-19 baseline stays empty."""
        proc = _run_cli("glt_tpu", "--rule=GLT023")
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# GLT024-026 protocol verification (two-endpoint fixture project)
# ---------------------------------------------------------------------------

# A minimal but idiomatic endpoint pair: a dispatch function (>= 2
# ``op ==`` compares), a protocol anchor branch, a binary-frame branch,
# and a POST_HELLO_OPS-gated op — the same shapes dist_server/dist_client
# use, shrunk to the recognizer's essentials.
_PROTO_SERVER = """
POST_HELLO_OPS = frozenset({"flight_dump"})
_KIND_MSG = 1

def handle(req, conn):
    op = req["op"]
    if op == "ping":
        return {"ok": True, "protocol": 1}
    if op == "flight_dump":
        return {"flight": []}
    if op == "fetch":
        conn.send_frame(_KIND_MSG, b"payload")
        return None
    raise ValueError(op)
"""

_PROTO_CLIENT_CLEAN = """
def run(conn):
    conn.request(op="ping", peer="me")
    conn.request(op="fetch", producer_id=1)
    try:
        return conn.request(op="flight_dump")
    except RuntimeError:
        return None
"""


class TestUnmatchedWireOp:
    def test_client_op_without_dispatch_branch_fires(self):
        client = _PROTO_CLIENT_CLEAN + textwrap.dedent("""
        def drifted(conn):
            try:
                conn.request(op="flight_dumpp")   # renamed server-side
            except RuntimeError:
                pass
        """)
        hits = project_findings(
            {"pkg.server": _PROTO_SERVER, "pkg.client": client},
            "unmatched-wire-op")
        assert len(hits) == 1
        assert "flight_dumpp" in hits[0].message
        assert "unknown-op" in hits[0].message

    def test_dead_dispatch_branch_fires(self):
        client = """
        def run(conn):
            conn.request(op="ping", peer="me")
            try:
                conn.request(op="flight_dump")
            except RuntimeError:
                pass
        """
        hits = project_findings(          # nobody sends "fetch"
            {"pkg.server": _PROTO_SERVER, "pkg.client": client},
            "unmatched-wire-op")
        assert len(hits) == 1
        assert "fetch" in hits[0].message
        assert "no in-tree client" in hits[0].message

    def test_matched_endpoints_clean(self):
        assert project_findings(
            {"pkg.server": _PROTO_SERVER,
             "pkg.client": _PROTO_CLIENT_CLEAN},
            "unmatched-wire-op") == []

    def test_client_only_file_set_is_silent(self):
        """No dispatch function in the analyzed set: nothing to resolve
        against, so nothing fires (a lint of dist_client alone must not
        claim every op is unmatched)."""
        assert project_findings(
            {"pkg.client": _PROTO_CLIENT_CLEAN}, "unmatched-wire-op") == []

    def test_suppression_comment(self):
        server = _PROTO_SERVER.replace(
            '    if op == "fetch":',
            '    # out-of-tree caller (operator tooling)\n'
            '    # gltlint: disable-next=unmatched-wire-op\n'
            '    if op == "fetch":')
        client = """
        def run(conn):
            conn.request(op="ping", peer="me")
            try:
                conn.request(op="flight_dump")
            except RuntimeError:
                pass
        """
        assert project_findings(
            {"pkg.server": server, "pkg.client": client},
            "unmatched-wire-op") == []


class TestUnclassifiedErrorCode:
    _SERVER_WITH_CODE = _PROTO_SERVER + textwrap.dedent("""
    def fail(conn, e):
        conn.send({"error": str(e), "code": "weird_fault"})
    """)

    def test_unrecognized_code_fires(self):
        hits = project_findings(
            {"pkg.server": self._SERVER_WITH_CODE,
             "pkg.client": _PROTO_CLIENT_CLEAN},
            "unclassified-error-code")
        assert len(hits) == 1
        assert "weird_fault" in hits[0].message

    def test_codes_set_membership_recognizes(self):
        client = _PROTO_CLIENT_CLEAN + textwrap.dedent("""
        FATAL_CODES = frozenset({"weird_fault"})
        """)
        assert project_findings(
            {"pkg.server": self._SERVER_WITH_CODE, "pkg.client": client},
            "unclassified-error-code") == []

    def test_typed_exception_code_attr_recognizes(self):
        client = _PROTO_CLIENT_CLEAN + textwrap.dedent("""
        class WeirdFault(RuntimeError):
            code = "weird_fault"
        """)
        assert project_findings(
            {"pkg.server": self._SERVER_WITH_CODE, "pkg.client": client},
            "unclassified-error-code") == []

    def test_explicit_comparison_recognizes(self):
        client = _PROTO_CLIENT_CLEAN + textwrap.dedent("""
        def classify(resp):
            if resp.get("code") == "weird_fault":
                raise RuntimeError("weird")
        """)
        assert project_findings(
            {"pkg.server": self._SERVER_WITH_CODE, "pkg.client": client},
            "unclassified-error-code") == []

    def test_getattr_field_selector_is_not_a_code(self):
        """``getattr(e, "code", "io_failed")``: only the default can flow
        into the wire code — the attribute name must not be inventoried
        (the calibration bug that flagged the string ``"code"``)."""
        server = _PROTO_SERVER + textwrap.dedent("""
        def fail(conn, e):
            conn.send({"error": str(e),
                       "code": getattr(e, "code", "io_failed")})
        """)
        client = _PROTO_CLIENT_CLEAN + textwrap.dedent("""
        IO_CODES = ("io_failed",)
        """)
        assert project_findings(
            {"pkg.server": server, "pkg.client": client},
            "unclassified-error-code") == []


class TestMissingMixedVersionFallback:
    def test_bare_gated_send_fires(self):
        client = """
        def run(conn):
            conn.request(op="ping", peer="me")
            return conn.request(op="flight_dump")   # no fallback
        """
        hits = project_findings(
            {"pkg.server": _PROTO_SERVER, "pkg.client": client},
            "missing-mixed-version-fallback")
        assert len(hits) == 1
        assert "flight_dump" in hits[0].message
        assert "protocol >= 1" in hits[0].message

    def test_guarded_send_clean(self):
        assert project_findings(
            {"pkg.server": _PROTO_SERVER,
             "pkg.client": _PROTO_CLIENT_CLEAN},
            "missing-mixed-version-fallback") == []

    def test_dict_built_outside_try_with_guarded_send_clean(self):
        """The profile_capture spelling: the request dict is assembled
        at the top of the function, the ``request(**req)`` send sits in
        the try — the site degrades even though the literal does not."""
        client = """
        def run(conn, millis):
            req = {"op": "flight_dump", "millis": millis}
            try:
                return conn.request(**req)
            except RuntimeError:
                return None
        """
        assert project_findings(
            {"pkg.server": _PROTO_SERVER, "pkg.client": client},
            "missing-mixed-version-fallback") == []

    def test_protocol0_ops_need_no_fallback(self):
        client = """
        def run(conn):
            return conn.request(op="ping", peer="me")
        """
        assert project_findings(
            {"pkg.server": _PROTO_SERVER, "pkg.client": client},
            "missing-mixed-version-fallback") == []


class TestOpTableExtraction:
    def _table(self):
        from glt_tpu.analysis.protocol import extract_op_table
        return extract_op_table(make_project(
            {"pkg.server": _PROTO_SERVER,
             "pkg.client": _PROTO_CLIENT_CLEAN}))

    def test_ops_and_protocol(self):
        table = self._table()
        assert set(table.ops) == {"ping", "fetch", "flight_dump"}
        assert table.protocol == 1

    def test_min_protocol_from_post_hello_ops(self):
        table = self._table()
        assert table.ops["flight_dump"].min_protocol == 1
        assert table.ops["ping"].min_protocol == 0

    def test_frame_kind_from_kind_constant(self):
        table = self._table()
        assert table.ops["fetch"].frame == "msg"
        assert table.ops["ping"].frame == "json"

    def test_request_and_response_keys(self):
        table = self._table()
        assert table.ops["ping"].request_keys == {"peer"}
        assert table.ops["fetch"].request_keys == {"producer_id"}
        assert table.ops["ping"].response_keys == {"ok", "protocol"}

    def test_markdown_matrix_rows(self):
        from glt_tpu.analysis.protocol import format_op_table
        text = format_op_table(self._table())
        assert "| `flight_dump` | json | 1 |" in text
        assert "| `fetch` | msg | 0 | producer_id | (msg frame) |" in text

    def test_real_tree_dump_lists_every_wire_op(self):
        """The acceptance bar: the dump over glt_tpu covers the full
        PR-19 protocol surface, fleet and serving ops included."""
        proc = _run_cli("--format=optable")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        for op in ("create_sampling_producer", "fetch_one_sampled_message",
                   "fleet_hello", "fleet_shed", "flight_dump",
                   "profile_capture", "subgraph_request", "heartbeat"):
            assert f"`{op}`" in proc.stdout, op

    def test_docs_matrix_matches_generated(self):
        """The committed block in docs/distributed.md IS the generated
        table (mirrors the CI drift check)."""
        import re
        proc = _run_cli("--format=optable")
        doc = open(os.path.join(REPO, "docs", "distributed.md")).read()
        m = re.search(r"<!-- optable:begin[^>]*-->\n(.*?)<!-- optable:end -->",
                      doc, re.S)
        assert m, "optable markers missing from docs/distributed.md"
        assert proc.stdout.strip() == m.group(1).strip()


# ---------------------------------------------------------------------------
# GLT027 unguarded-shared-field
# ---------------------------------------------------------------------------

class TestUnguardedSharedField:
    def test_rmw_missing_the_fields_lock_fires(self):
        """The serving/front.py calibration catch: an EWMA read-modify-
        write outside the lock its reader holds."""
        src = """
        import threading

        class Front:
            def __init__(self):
                self._stats_lock = threading.Lock()
                self._ewma = 0.0
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def _loop(self):
                while True:
                    self._ewma += 0.1

            def stats(self):
                with self._stats_lock:
                    return {"ewma": self._ewma}
        """
        hits = project_findings({"pkg.front": src},
                                "unguarded-shared-field")
        assert len(hits) == 1
        assert "_ewma" in hits[0].message
        assert "misses the field's locking discipline" in hits[0].message

    def test_inconsistent_locking_fires(self):
        src = """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    with self._lock:
                        self._n += 1

            def bump(self):
                self._n += 1
        """
        hits = project_findings({"pkg.w": src}, "unguarded-shared-field")
        assert len(hits) == 1
        assert "inconsistent locking" in hits[0].message

    def test_multi_domain_lockfree_writes_fire(self):
        src = """
        import threading

        class W:
            def __init__(self):
                self._n = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    self._n += 1

            def bump(self):
                self._n += 1
        """
        hits = project_findings({"pkg.w": src}, "unguarded-shared-field")
        assert len(hits) == 1
        assert "multiple thread domains" in hits[0].message

    def test_atomic_publish_via_replace_exempt(self):
        """Single-writer plain assigns (the fleet_shed ``_shed_frac``
        idiom): readers see old-or-new, never torn."""
        src = """
        import threading

        class W:
            def __init__(self):
                self._frac = 0.0
                threading.Thread(target=self._loop).start()

            def set_frac(self, f):
                self._frac = float(f)

            def _loop(self):
                while True:
                    print(self._frac)
        """
        assert project_findings({"pkg.w": src},
                                "unguarded-shared-field") == []

    def test_single_writer_counter_exempt(self):
        """RMW counters owned by one thread with no locked access
        anywhere (the HeartbeatSender ``sent`` idiom)."""
        src = """
        import threading

        class W:
            def __init__(self):
                self.sent = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    self.sent += 1

            def read(self):
                return self.sent
        """
        assert project_findings({"pkg.w": src},
                                "unguarded-shared-field") == []

    def test_queue_handoff_exempt(self):
        src = """
        import queue
        import threading

        class W:
            def __init__(self):
                self._q = queue.Queue(maxsize=8)
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    self._q.put(1, timeout=1.0)

            def drain(self):
                return self._q.get(timeout=1.0)
        """
        assert project_findings({"pkg.w": src},
                                "unguarded-shared-field") == []

    def test_common_lock_over_all_writes_clean(self):
        src = """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    with self._lock:
                        self._n += 1

            def bump(self):
                with self._lock:
                    self._n += 1
        """
        assert project_findings({"pkg.w": src},
                                "unguarded-shared-field") == []

    def test_no_thread_entries_is_silent(self):
        """Without a ``Thread(target=...)`` spawn the class is
        single-threaded by construction — nothing to check."""
        src = """
        class W:
            def __init__(self):
                self._n = 0

            def bump(self):
                self._n += 1
        """
        assert project_findings({"pkg.w": src},
                                "unguarded-shared-field") == []

    def test_suppression_comment(self):
        src = """
        import threading

        class W:
            def __init__(self):
                self._n = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    # benign drift: approximate stat
                    # gltlint: disable-next=unguarded-shared-field
                    self._n += 1

            def bump(self):
                self._n += 1
        """
        assert project_findings({"pkg.w": src},
                                "unguarded-shared-field") == []


def test_protocol_rules_clean_on_distributed_and_serving():
    """Real-tree smoke: the fleet contracts verify clean — the op table
    resolves, every server code classifies, every gated send degrades,
    every shared field is locked or sanctioned."""
    proc = _run_cli("glt_tpu",
                    "--select=GLT024,GLT025,GLT026,GLT027")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


def test_device_program_rules_clean_on_ops_and_parallel():
    """Real-tree smoke: the device-program passes (GLT017-021) verify
    every committed kernel and shard_map body with zero findings —
    GLT017 covers every candidate_{gather,sample}_params point."""
    proc = subprocess.run(
        [sys.executable, "-m", "glt_tpu.analysis",
         "glt_tpu/ops", "glt_tpu/parallel",
         "--select=GLT017,GLT018,GLT019,GLT020,GLT021"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


# ---------------------------------------------------------------------------
# the gate itself
# ---------------------------------------------------------------------------

def test_rule_registry_complete():
    assert set(RULES) == {
        "host-sync-in-jit", "prng-key-reuse", "recompile-hazard",
        "int64-id-truncation", "nondeterministic-default-rng",
        "shadowed-jit-donation", "unbounded-blocking-get",
        "lock-order-inversion", "blocking-call-while-holding-lock",
        "span-in-traced-code", "non-atomic-state-publish",
        "unbounded-queue-put", "dispatch-in-epoch-loop",
        "blocking-io-in-epoch-loop", "wall-clock-duration",
        "unbalanced-profiler-capture",
        "vmem-budget-exceeded", "unbalanced-dma-ring",
        "unaligned-tile-shape", "divergent-collective",
        "unknown-axis-name", "lossy-dtype-narrowing",
        "unjittered-retry-loop",
        "unmatched-wire-op", "unclassified-error-code",
        "missing-mixed-version-fallback", "unguarded-shared-field",
    }


def test_cli_clean_on_glt_tpu():
    """The shipped tree must lint clean: ``python -m glt_tpu.analysis
    glt_tpu`` exits 0 (the CI gate), with the interprocedural passes on."""
    proc = subprocess.run(
        [sys.executable, "-m", "glt_tpu.analysis", "glt_tpu"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


# The perf guards judge CPU time, in units of work: a wall clock around a
# child stretches with whatever else the machine runs (the suite runs six
# workers wide), and a budget in seconds means another thing on every
# machine.  One unit is one pass that parses every source file under
# ``glt_tpu/`` and walks its tree, measured the same way back to back.
_PARSE_WALKS = 5
_PARSE_WALK_SRC = f"""
import ast, os
for _ in range({_PARSE_WALKS}):
    for d, _, names in os.walk("glt_tpu"):
        for n in names:
            if n.endswith(".py"):
                with open(os.path.join(d, n)) as fh:
                    tree = ast.parse(fh.read())
                sum(1 for _ in ast.walk(tree))
"""


def _child_cpu_s(argv):
    """``(proc, seconds)``: a child run to its end and the CPU time (user
    + system) it used, from this process's reaped-children rusage."""
    import resource
    before = resource.getrusage(resource.RUSAGE_CHILDREN)
    proc = subprocess.run(argv, cwd=REPO, capture_output=True, text=True,
                          timeout=600)
    after = resource.getrusage(resource.RUSAGE_CHILDREN)
    return proc, ((after.ru_utime - before.ru_utime)
                  + (after.ru_stime - before.ru_stime))


def _lint_cost(*args):
    """``(proc, passes)`` of one gltlint run over the repo: its CPU time
    in parse-and-walk passes over ``glt_tpu/``."""
    unit, unit_s = _child_cpu_s([sys.executable, "-c", _PARSE_WALK_SRC])
    assert unit.returncode == 0, unit.stderr
    proc, lint_s = _child_cpu_s(
        [sys.executable, "-m", "glt_tpu.analysis", *args])
    return proc, lint_s / (unit_s / _PARSE_WALKS)


# Measured at PR 28: the whole analysis 17-21 passes, GLT024 alone 5-6.
WHOLE_BUDGET_PASSES = 40.0
SINGLE_RULE_BUDGET_PASSES = 20.0


def test_cli_perf_guard():
    """The whole-project analysis (symbols + call graph + effects + all
    rules) must stay under its budget, and no single rule pass may eat
    more than half of it."""
    proc, cost = _lint_cost("glt_tpu", "--profile")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert cost < WHOLE_BUDGET_PASSES, (
        f"gltlint cost {cost:.1f} parse passes "
        f"(budget {WHOLE_BUDGET_PASSES:.0f})")
    assert "total" in proc.stderr       # --profile prints pass timings
    # per-rule rows: "gltlint --profile:   pass <name>   <ms> ms"
    passes, total_ms = {}, None
    for line in proc.stderr.splitlines():
        parts = line.split()
        if "pass" in parts and parts[-1] == "ms":
            passes[parts[parts.index("pass") + 1]] = float(parts[-2])
        elif "total" in parts and parts[-1] == "ms":
            total_ms = float(parts[-2])
    assert "vmem-budget-exceeded" in passes     # new passes are timed
    assert "divergent-collective" in passes
    assert "unmatched-wire-op" in passes        # v4 protocol pass
    assert "unguarded-shared-field" in passes   # v4 threads pass
    for name, ms in passes.items():
        share = ms / total_ms           # of the child's own clock
        assert share * cost < WHOLE_BUDGET_PASSES / 2, (
            f"pass {name} took {share:.0%} of a run of {cost:.1f} passes")
    # incremental mode shares the same budget and reports its slice
    proc, cost = _lint_cost("glt_tpu", "--since=HEAD", "--profile")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert ("incremental slice:" in proc.stderr
            or "needs git" in proc.stderr)      # git-less env falls back
    assert cost < WHOLE_BUDGET_PASSES, f"--since run cost {cost:.1f} passes"


def test_cli_flags_a_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "glt_tpu.analysis", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "GLT001" in proc.stdout


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "glt_tpu.analysis", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for code in ("GLT001", "GLT002", "GLT003", "GLT004", "GLT005",
                 "GLT006", "GLT007", "GLT008", "GLT009",
                 "GLT017", "GLT018", "GLT019", "GLT020", "GLT021",
                 "GLT024", "GLT025", "GLT026", "GLT027"):
        assert code in proc.stdout


def test_cli_single_rule_mode():
    """``--rule`` runs exactly one pass without the call-graph build —
    the sub-second inner loop while burning down one finding class."""
    proc = _run_cli("glt_tpu/ops", "--rule=GLT017")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stdout


def test_cli_single_rule_glt024_under_profile_guard():
    """The op-table extraction is a project-wide pass; single-rule mode
    over the whole tree must still clear half the whole run's budget."""
    proc, cost = _lint_cost("glt_tpu", "--rule=GLT024", "--profile")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert cost < SINGLE_RULE_BUDGET_PASSES, (
        f"--rule=GLT024 cost {cost:.1f} parse passes "
        f"(budget {SINGLE_RULE_BUDGET_PASSES:.0f})")


def _git(*args, cwd):
    subprocess.run(
        ["git", "-c", "user.name=t", "-c", "user.email=t@t.invalid",
         *args],
        cwd=cwd, check=True, capture_output=True)


def test_cli_changed_mode_slices_to_dirty_files(tmp_path):
    """``--changed`` lints only what git reports dirty vs HEAD: a
    committed violation stays quiet until the file itself changes,
    while untracked files are always in the slice."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)
    """))
    _git("init", "-q", cwd=tmp_path)
    _git("add", "-A", cwd=tmp_path)
    _git("commit", "-qm", "seed", cwd=tmp_path)
    clean = tmp_path / "clean.py"           # untracked, violation-free
    clean.write_text("x = 1\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "glt_tpu.analysis",
             str(bad), str(clean), *extra],
            cwd=tmp_path, env=env, capture_output=True, text=True,
            timeout=120)

    proc = run()                            # full run: violation fires
    assert proc.returncode == 1 and "GLT001" in proc.stdout
    proc = run("--changed", "--profile")    # slice: only clean.py dirty
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "incremental slice: 1 changed file(s)" in proc.stderr
    bad.write_text(bad.read_text() + "\n# touched\n")
    proc = run("--changed")                 # now bad.py is in the slice
    assert proc.returncode == 1 and "GLT001" in proc.stdout


def test_cli_rule_rejects_lists_and_select():
    proc = _run_cli("glt_tpu/ops", "--rule=GLT017,GLT018")
    assert proc.returncode == 2
    assert "exactly one rule" in proc.stderr
    proc = _run_cli("glt_tpu/ops", "--rule=GLT017", "--select=GLT018")
    assert proc.returncode == 2
    assert "mutually exclusive" in proc.stderr


# ---------------------------------------------------------------------------
# output formats + baseline
# ---------------------------------------------------------------------------

BAD_JIT = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.asarray(x)
"""


def _run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "glt_tpu.analysis", *args],
        cwd=cwd, capture_output=True, text=True, timeout=120)


class TestOutputFormats:
    def test_json_format(self, tmp_path):
        import json
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(BAD_JIT))
        proc = _run_cli(str(bad), "--format=json")
        assert proc.returncode == 1
        data = json.loads(proc.stdout)
        assert data["summary"]["errors"] == 1
        (f,) = data["findings"]
        assert f["code"] == "GLT001" and f["severity"] == "error"
        assert f["line"] > 0 and f["path"] == str(bad)

    def test_github_format(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(BAD_JIT))
        proc = _run_cli(str(bad), "--format=github")
        assert proc.returncode == 1
        assert "::error file=" in proc.stdout
        assert "title=GLT001" in proc.stdout

    def test_github_format_escapes_newlines(self):
        from glt_tpu.analysis.report import Finding, format_github
        f = Finding(path="a.py", line=1, col=1, rule="r", code="GLT001",
                    severity=Severity.ERROR, message="line1\nline2 100%")
        out = format_github([f])
        assert "%0A" in out and "%25" in out and "\nline2" not in out


class TestBaseline:
    def test_write_then_gate_only_on_new(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(BAD_JIT))
        baseline = tmp_path / "baseline.json"
        proc = _run_cli(str(bad), "--write-baseline", str(baseline))
        assert proc.returncode == 0 and baseline.exists()
        # the recorded finding no longer gates
        proc = _run_cli(str(bad), "--baseline", str(baseline))
        assert proc.returncode == 0, proc.stdout
        assert "baselined finding(s) hidden" in proc.stdout
        # ... a new finding still does
        bad.write_text(textwrap.dedent(BAD_JIT) + textwrap.dedent("""
            @jax.jit
            def g(y):
                return y.sum().item()
        """))
        proc = _run_cli(str(bad), "--baseline", str(baseline))
        assert proc.returncode == 1
        assert ".item()" in proc.stdout          # only the new finding
        assert "np.asarray" not in proc.stdout   # old one stays hidden

    def test_baseline_keys_survive_line_drift(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(BAD_JIT))
        baseline = tmp_path / "baseline.json"
        _run_cli(str(bad), "--write-baseline", str(baseline))
        # prepend unrelated code: every line number shifts
        bad.write_text("UNRELATED = 1\n\n" + textwrap.dedent(BAD_JIT))
        proc = _run_cli(str(bad), "--baseline", str(baseline))
        assert proc.returncode == 0, proc.stdout

    def test_missing_baseline_is_usage_error(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("x = 1\n")
        proc = _run_cli(str(bad), "--baseline",
                        str(tmp_path / "nope.json"))
        assert proc.returncode == 2

    def test_committed_baseline_is_empty(self):
        """The shipped baseline proves the tree lints clean today — new
        findings must be fixed or suppressed, not silently baselined."""
        import json
        with open(os.path.join(REPO, ".gltlint-baseline.json")) as fh:
            data = json.load(fh)
        assert data["findings"] == []
