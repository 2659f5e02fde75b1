"""JAX-aware repo lint (pinot_tpu.analysis.repo_lint).

Each rule fires on a minimal fixture snippet and stays quiet on the
locked/hoisted counterpart; the live pinot_tpu tree must be clean."""
import textwrap

from pinot_tpu.analysis.repo_lint import Finding, lint_source, lint_tree


def _lint(src, threaded=False):
    return lint_source(textwrap.dedent(src), path="fixture.py", threaded=threaded)


def _rules(src, threaded=False):
    return [f.rule for f in _lint(src, threaded=threaded)]


class TestW001FloatLiteralInKernel:
    def test_flags_float_literal_in_jitted_arithmetic(self):
        src = """
        import jax

        def kernel(x):
            return x * 0.5

        fn = jax.jit(kernel)
        """
        assert _rules(src) == ["W001"]

    def test_flags_float_comparison_under_decorator(self):
        src = """
        import jax

        @jax.jit
        def kernel(x):
            return x > 1.5
        """
        assert _rules(src) == ["W001"]

    def test_quiet_outside_kernels_and_on_int_literals(self):
        src = """
        import jax

        def helper(x):
            return x * 0.5  # not jitted: host-side is fine

        def kernel(x):
            return x * 2

        fn = jax.jit(kernel)
        """
        assert _rules(src) == []


class TestW002HostSyncInKernel:
    def test_flags_item_and_np_asarray(self):
        src = """
        import jax
        import numpy as np

        def kernel(x):
            n = x.sum().item()
            return np.asarray(x) + n

        fn = jax.jit(kernel)
        """
        assert _rules(src) == ["W002", "W002"]

    def test_quiet_on_jnp_asarray(self):
        src = """
        import jax
        import jax.numpy as jnp

        def kernel(x):
            return jnp.asarray(x)

        fn = jax.jit(kernel)
        """
        assert _rules(src) == []


class TestW002PallasKernelAndLaunchLoop:
    def test_flags_host_numpy_inside_pallas_kernel_body(self):
        src = """
        import numpy as np
        from jax.experimental import pallas as pl

        def scan_kernel(x_ref, o_ref):
            o_ref[...] = np.cumsum(x_ref[...])

        def run(x):
            return pl.pallas_call(scan_kernel, out_shape=x)(x)
        """
        assert _rules(src) == ["W002"]

    def test_quiet_on_np_outside_kernel_body(self):
        src = """
        import numpy as np
        from jax.experimental import pallas as pl

        def scan_kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x):
            shape = np.zeros(4)  # host setup around the launch is fine
            return pl.pallas_call(scan_kernel, out_shape=x)(x)
        """
        assert _rules(src) == []

    def test_flags_block_until_ready_in_launch_loop(self):
        src = """
        def run(fn, batches):
            outs = []
            for cols, params in batches:
                outs.append(fn(cols, params).block_until_ready())
            return outs
        """
        assert _rules(src) == ["W002"]

    def test_quiet_on_hoisted_sync_and_device_get_in_loop(self):
        src = """
        import jax

        def run(fn, batches):
            outs = [fn(c, p) for c, p in batches]
            for o in outs:
                jax.device_get(o)  # fetch is a completion fence, not a stall
            return outs[-1].block_until_ready()
        """
        assert _rules(src) == []


class TestW003JitInLoop:
    def test_flags_jit_inside_loop_body(self):
        src = """
        import jax

        def run(fns, x):
            outs = []
            for f in fns:
                outs.append(jax.jit(f)(x))
            return outs
        """
        assert "W003" in _rules(src)

    def test_quiet_when_hoisted(self):
        src = """
        import jax

        def run(f, xs):
            g = jax.jit(f)
            return [g(x) for x in xs]
        """
        assert _rules(src) == []

    def test_def_inside_loop_resets_scope(self):
        src = """
        import jax

        for name in ("a", "b"):
            def make(f):
                return jax.jit(f)
        """
        assert _rules(src) == []


class TestW004UnlockedSharedRMW:
    def test_flags_augassign_on_self_attr(self):
        src = """
        class Broker:
            def route(self):
                self._rr += 1
        """
        assert _rules(src, threaded=True) == ["W004"]

    def test_flags_alias_bucket_write(self):
        # the exact broker token-bucket race shape from ADVICE r5
        src = """
        class Quota:
            def check(self, table):
                b = self._buckets.get(table)
                b[0] = b[0] - 1
        """
        assert _rules(src, threaded=True) == ["W004"]

    def test_quiet_under_lock(self):
        src = """
        class Broker:
            def route(self):
                with self._lock:
                    self._rr += 1
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_on_plain_insert_and_init(self):
        src = """
        class Broker:
            def __init__(self):
                self._rr = 0

            def register(self, name, server):
                self.servers[name] = server
        """
        assert _rules(src, threaded=True) == []

    def test_w004_requires_threaded_scope(self):
        src = """
        class Planner:
            def bump(self):
                self._n += 1
        """
        assert _rules(src, threaded=False) == []


class TestW005WallClockInElapsedMath:
    def test_flags_time_time_subtraction(self):
        src = """
        import time

        def age(started):
            return time.time() - started
        """
        assert _rules(src) == ["W005"]

    def test_flags_aliased_wall_clock_in_comparison(self):
        src = """
        import time

        def expired(deadline):
            now = time.time()
            return now >= deadline
        """
        assert _rules(src) == ["W005"]

    def test_quiet_on_monotonic_and_epoch_stamps(self):
        src = """
        import time

        def age(started):
            return time.monotonic() - started

        def creation_time_ms():
            return int(time.time() * 1000)

        def stamp():
            return time.time()
        """
        assert _rules(src) == []

    def test_alias_in_other_scope_does_not_leak(self):
        src = """
        import time

        def stamp():
            now = time.time()
            return now

        def age(now, started):
            return now - started
        """
        assert _rules(src) == []


class TestW006SwallowedClusterException:
    def test_flags_except_continue_without_recording(self):
        src = """
        def scatter(servers):
            out = []
            for s in servers:
                try:
                    out.append(s.execute())
                except Exception:
                    continue
            return out
        """
        assert _rules(src, threaded=True) == ["W006"]

    def test_flags_silent_pass(self):
        src = """
        def drop(self, name):
            try:
                self._close(name)
            except Exception:
                pass
        """
        assert _rules(src, threaded=True) == ["W006"]

    def test_quiet_when_recorded_or_reraised(self):
        src = """
        import logging

        def scatter(self, servers):
            for s in servers:
                try:
                    s.execute()
                except KeyError:
                    raise
                except Exception:
                    logging.exception("server %s failed", s)
        """
        assert _rules(src, threaded=True) == []

    def test_w006_requires_cluster_scope(self):
        src = """
        def best_effort(x):
            try:
                return int(x)
            except ValueError:
                pass
        """
        assert _rules(src, threaded=False) == []


class TestW007UnboundedMetricName:
    def test_flags_sql_in_counter_name(self):
        src = """
        def record(self, sql):
            METRICS.counter(f"latency.{sql}").inc()
        """
        assert _rules(src) == ["W007"]

    def test_flags_query_id_in_span_name(self):
        src = """
        def run(self, trace, query_id):
            with trace.span(f"exec:{query_id}"):
                pass
        """
        assert _rules(src) == ["W007"]

    def test_flags_attribute_access_and_bare_id(self):
        src = """
        def run(self, ctx):
            METRICS.histogram(f"lat.{ctx.fingerprint}").update(1)
            METRICS.gauge(f"g.{id}").set(1)
        """
        assert _rules(src) == ["W007", "W007"]

    def test_quiet_on_bounded_label_spaces(self):
        src = """
        def record(self, table, server, seg):
            METRICS.gauge(f"server.segmentBytes.{table}").add(1)
            METRICS.counter(f"broker.breakerOpen.{server}").inc()
            with self.trace.span(f"launch:{seg.name}"):
                pass
        """
        assert _rules(src) == []

    def test_quiet_on_plain_string_names_and_non_sinks(self):
        src = """
        def record(self, sql):
            METRICS.counter("broker.queries").inc()
            log(f"ran {sql}")  # not a metric/span name sink
        """
        assert _rules(src) == []


class TestW008LiteralFingerprintInPlanCacheKey:
    def test_flags_fingerprint_in_cache_get_key(self):
        src = """
        def plan(self, ctx, seg):
            return self._plan_cache.get((ctx.fingerprint(), seg.signature()))
        """
        assert _rules(src) == ["W008"]

    def test_flags_fingerprint_via_key_alias(self):
        src = """
        def plan(ctx, seg):
            key = (ctx.fingerprint(), seg.signature())
            cached = _PLAN_CACHE.get(key)
            return cached
        """
        assert _rules(src) == ["W008"]

    def test_flags_subscript_store(self):
        src = """
        def plan(self, ctx, plan):
            self._plan_cache[ctx.fingerprint()] = plan
        """
        assert _rules(src) == ["W008"]

    def test_quiet_on_shape_fingerprint_key(self):
        src = """
        def plan(self, ctx, seg):
            key = (ctx.shape_fingerprint(), seg.signature())
            return self._plan_cache.get(key)
        """
        assert _rules(src) == []

    def test_quiet_on_non_plan_cache_sinks(self):
        src = """
        def execute(self, ctx, table):
            ckey = (table, ctx.fingerprint())
            hit = self.result_cache.get(ckey)
            self.slow_queries.record(ctx.sql, ctx.fingerprint())
            return hit
        """
        assert _rules(src) == []

    def test_alias_in_other_scope_does_not_leak(self):
        src = """
        def make_key(ctx):
            key = ctx.fingerprint()
            return key

        def plan(self, key):
            return self._plan_cache.get(key)
        """
        assert _rules(src) == []


class TestW015UnboundedServingGrowth:
    def test_flags_list_append_in_serving_method(self):
        src = """
        class Broker:
            def __init__(self):
                self.audit = []

            def execute(self, ctx):
                self.audit.append(ctx.sql)
        """
        assert _rules(src, threaded=True) == ["W015"]

    def test_flags_dict_keyed_by_query_id(self):
        src = """
        class Broker:
            def __init__(self):
                self.results = {}

            def handle(self, query_id, rows):
                self.results[query_id] = rows
        """
        assert _rules(src, threaded=True) == ["W015"]

    def test_flags_setdefault_keyed_by_request_value(self):
        src = """
        class Server:
            def __init__(self):
                self.inflight = dict()

            def do_POST(self, qid, fut):
                self.inflight.setdefault(qid, fut)
        """
        assert _rules(src, threaded=True) == ["W015"]

    def test_quiet_on_bounded_deque(self):
        src = """
        from collections import deque

        class Broker:
            def __init__(self):
                self.audit = deque(maxlen=128)

            def execute(self, ctx):
                self.audit.append(ctx.sql)
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_with_eviction_evidence(self):
        src = """
        class Server:
            def __init__(self):
                self.inflight = {}

            def handle(self, query_id, fut):
                self.inflight[query_id] = fut

            def finish(self, query_id):
                self.inflight.pop(query_id, None)
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_when_reassigned_outside_init(self):
        src = """
        class Broker:
            def __init__(self):
                self.batch = []

            def execute(self, ctx):
                self.batch.append(ctx.sql)

            def flush(self):
                self.batch = []
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_on_bounded_label_key_and_setup_methods(self):
        src = """
        class Coordinator:
            def __init__(self):
                self.tables = {}
                self.listeners = []

            def handle(self, table, meta):
                self.tables[table] = meta  # bounded label space

            def register(self, cb):
                self.listeners.append(cb)  # setup, not serving
        """
        assert _rules(src, threaded=True) == []

    def test_rule_is_threaded_scope_only(self):
        src = """
        class Recorder:
            def __init__(self):
                self.rows = []

            def record(self, row):
                self.rows.append(row)
        """
        assert _rules(src, threaded=False) == []
        assert _rules(src, threaded=True) == ["W015"]


class TestW016DurableWriteDiscipline:
    def test_flags_in_place_write_to_checkpoint_path(self):
        src = """
        import json

        def save(state, path):
            with open(path + "/checkpoint.json", "w") as f:
                json.dump(state, f)
        """
        assert _rules(src) == ["W016"]

    def test_flags_bare_write_in_commit_function(self):
        src = """
        import json

        def commit_state(state, path):
            with open(path, "w") as f:
                json.dump(state, f)
        """
        assert _rules(src) == ["W016"]

    def test_flags_binary_manifest_write(self):
        src = """
        def dump(blob, d):
            with open(d + "/manifest.bin", "wb") as f:
                f.write(blob)
        """
        assert _rules(src) == ["W016"]

    def test_quiet_with_tmp_fsync_replace_discipline(self):
        src = """
        import json, os

        def commit_checkpoint(state, path):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(state, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        """
        assert _rules(src) == []

    def test_quiet_with_durable_write_helper(self):
        src = """
        from pinot_tpu.spi.filesystem import durable_write_json

        def commit_checkpoint(state, path):
            durable_write_json(path, state)
        """
        assert _rules(src) == []

    def test_quiet_on_non_durable_paths_and_reads(self):
        src = """
        import json

        def export_report(rows, path):
            with open(path + "/report.csv", "w") as f:
                f.write(rows)

        def load_checkpoint(path):
            with open(path + "/checkpoint.json") as f:
                return json.load(f)
        """
        assert _rules(src) == []

    def test_runs_unthreaded_everywhere(self):
        src = """
        def write_journal(entries, path):
            with open(path, "w") as f:
                f.writelines(entries)
        """
        assert _rules(src, threaded=False) == ["W016"]
        assert _rules(src, threaded=True) == ["W016"]


class TestW017UnfencedDispatchTiming:
    def test_flags_perf_counter_around_jitted_name_call(self):
        src = """
        import time
        import jax

        def kernel(x):
            return x + x

        kernel_jit = jax.jit(kernel)

        def bench(x):
            t0 = time.perf_counter()
            y = kernel_jit(x)
            dt = time.perf_counter() - t0
            return y, dt
        """
        assert _rules(src) == ["W017"]

    def test_flags_monotonic_around_decorated_jit(self):
        src = """
        import time
        import jax

        @jax.jit
        def kernel(x):
            return x + x

        def bench(x):
            t0 = time.monotonic()
            y = kernel(x)
            return time.monotonic() - t0
        """
        assert _rules(src) == ["W017"]

    def test_quiet_with_fence_before_stop(self):
        src = """
        import time
        import jax

        def kernel(x):
            return x + x

        kernel_jit = jax.jit(kernel)

        def bench(x):
            t0 = time.perf_counter()
            y = kernel_jit(x)
            y.block_until_ready()
            dt = time.perf_counter() - t0
            return y, dt
        """
        assert _rules(src) == []

    def test_quiet_with_fence_wrapping_dispatch(self):
        src = """
        import time
        import jax

        def kernel(x):
            return x + x

        kernel_jit = jax.jit(kernel)

        def bench(x):
            t0 = time.perf_counter()
            y = jax.device_get(kernel_jit(x))
            dt = time.perf_counter() - t0
            return y, dt
        """
        assert _rules(src) == []

    def test_quiet_on_attribute_call_dispatch(self):
        # timing plan.fn(...) is the engine's compile_ms capture — the
        # dispatch cost IS the measurement there, so attr calls are out of
        # scope by design
        src = """
        import time
        import jax

        def kernel(x):
            return x + x

        kernel_jit = jax.jit(kernel)

        def launch(plan, x):
            t0 = time.perf_counter()
            y = plan.fn(x)
            dt = time.perf_counter() - t0
            return y, dt
        """
        assert _rules(src) == []

    def test_quiet_without_timer_or_without_dispatch(self):
        src = """
        import time
        import jax

        def kernel(x):
            return x + x

        kernel_jit = jax.jit(kernel)

        def run(x):
            return kernel_jit(x)

        def host_only():
            t0 = time.perf_counter()
            total = sum(range(100))
            return time.perf_counter() - t0, total
        """
        assert _rules(src) == []


class TestW019RetryLoopDiscipline:
    def test_flags_retry_loop_without_backoff(self):
        src = """
        def scatter(server, ctx, segs, cancel):
            while segs:
                res = server.execute(ctx, segs, cancel=cancel)
                segs = res.failed
        """
        assert _rules(src, threaded=True) == ["W019"]

    def test_flags_reissue_without_cancel_probe(self):
        src = """
        import time

        def scatter(server, ctx, segs):
            while segs:
                res = server.execute(ctx, segs)
                segs = res.failed
                time.sleep(0.002)
        """
        assert _rules(src, threaded=True) == ["W019"]

    def test_only_a_server_call_makes_a_loop_a_retry_loop(self):
        # `.execute(...)` by name: a per-segment executor call in a drain
        # loop is no scatter, whatever keywords it lacks
        src = """
        def drain(executor, ctx, segs):
            out = []
            while segs:
                out.append(executor.execute_segment(ctx, segs.pop()))
            return out
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_on_backoff_plus_cancel(self):
        src = """
        def scatter(self, server, ctx, segs, cancel):
            while segs:
                res = server.execute(ctx, segs, cancel=cancel)
                segs = res.failed
                self._sleep(0.002)
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_on_fan_out_for_loop(self):
        src = """
        def fan_out(servers, ctx):
            out = []
            for server in servers:
                out.append(server.execute(ctx, ["seg"]))
            return out
        """
        assert _rules(src, threaded=True) == []

    def test_quiet_on_nested_cancel_closure(self):
        src = """
        def scatter(self, server, ctx, segs, cancel):
            while segs:
                def run_one(name, _segs=segs):
                    return server.execute(ctx, _segs, cancel=cancel)
                segs = self._hedged(run_one)
                self._sleep(0.001)
        """
        assert _rules(src, threaded=True) == []

    def test_rule_is_threaded_scope_only(self):
        src = """
        def scatter(server, ctx, segs):
            while segs:
                segs = server.execute(ctx, segs).failed
        """
        assert _rules(src, threaded=False) == []
        assert sorted(set(_rules(src, threaded=True))) == ["W019"]


class TestW020PackedWidenBeforeUnpack:
    def test_flags_astype_on_packed_words_without_shift(self):
        src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def scan_kernel(words_ref, o_ref):
            packed = words_ref[...]
            wide = packed.astype(jnp.int32)  # widens BEFORE the lane unpack
            o_ref[...] = wide & 0xF

        def run(x):
            return pl.pallas_call(scan_kernel, out_shape=x)(x)
        """
        assert _rules(src) == ["W020"]

    def test_flags_ref_read_named_words(self):
        src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def scan_kernel(refs, o_ref):
            key_words = refs[...]
            o_ref[...] = key_words.astype(jnp.float32)

        def run(x):
            return pl.pallas_call(scan_kernel, out_shape=x)(x)
        """
        assert _rules(src) == ["W020"]

    def test_quiet_when_shift_precedes_cast(self):
        src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def scan_kernel(words_ref, o_ref):
            packed = words_ref[...]
            lanes = (packed[:, None] >> jnp.uint32(4)) & jnp.uint32(0xF)
            o_ref[...] = lanes.astype(jnp.int32)  # cast AFTER the unpack

        def run(x):
            return pl.pallas_call(scan_kernel, out_shape=x)(x)
        """
        assert _rules(src) == []

    def test_quiet_on_unpacked_operand_cast(self):
        src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def scan_kernel(key_ref, o_ref):
            o_ref[...] = key_ref[...].astype(jnp.int32)  # plain codes, not packed

        def run(x):
            return pl.pallas_call(scan_kernel, out_shape=x)(x)
        """
        assert _rules(src) == []

    def test_rule_scope_is_pallas_kernels_only(self):
        src = """
        import jax
        import jax.numpy as jnp

        def host_helper(packed_words):
            return packed_words.astype(jnp.int64)  # jit kernel, not Pallas

        fn = jax.jit(host_helper)
        """
        assert _rules(src) == []


class TestW021UnbudgetedSegmentDevicePut:
    def test_flags_bare_device_put_of_segment_codes(self):
        src = """
        import jax

        def serve(segment_codes, device):
            return jax.device_put(segment_codes, device)
        """
        assert _rules(src) == ["W021"]

    def test_flags_attribute_operand(self):
        src = """
        import jax

        def pin(self, device):
            return jax.device_put(self.values, device)
        """
        assert _rules(src) == ["W021"]

    def test_quiet_inside_staging_scopes(self):
        src = """
        import jax

        def to_device(self, device):
            return jax.device_put(self.codes, device)

        def _stage_entry(plan_packed, device):
            return jax.device_put(plan_packed, device)
        """
        assert _rules(src) == []

    def test_quiet_on_small_per_query_params(self):
        src = """
        import jax

        def dispatch(v, params, device):
            a = jax.device_put(v, device)
            b = jax.device_put(params, device)
            return a, b
        """
        assert _rules(src) == []

    def test_nested_non_staging_helper_is_not_exempt(self):
        src = """
        import jax

        def to_device(self, device):
            def pin_all(column_arrays):
                return jax.device_put(column_arrays, device)
            return pin_all(self.columns)
        """
        assert _rules(src) == ["W021"]


class TestW022WallClockInLeaseCode:
    def test_flags_deadline_addition_in_lease_class(self):
        # the exact bug W005 misses: lease deadline built by ADDITION
        src = """
        import time

        class LeaseManager:
            def acquire(self, ttl_s):
                return time.time() + ttl_s
        """
        assert _rules(src) == ["W022"]

    def test_flags_alias_compare_in_election_function(self):
        src = """
        import time

        def run_election_tick(lease):
            now = time.time()
            return lease.expires_at <= now
        """
        # W005 also fires on the comparison; W022 must be among the findings
        assert "W022" in _rules(src)

    def test_flags_epoch_identifier_mix_outside_scoped_names(self):
        src = """
        import time

        def check_fresh(entry_epoch, ttl_s):
            return entry_epoch > time.time() - ttl_s
        """
        assert "W022" in _rules(src)

    def test_quiet_on_injectable_clock_in_lease_code(self):
        src = """
        class LeaseManager:
            def acquire(self, ttl_s):
                deadline = self.clock() + ttl_s
                return deadline

            def expired(self, lease):
                return lease.expires_at <= self.now()
        """
        assert _rules(src) == []

    def test_quiet_on_epoch_timestamp_stamping_and_retention_math(self):
        # epoch-millis stamping is multiplication; retention math never
        # mixes time.time() into the same expression — both clean
        src = """
        import time

        def seal(segment):
            segment.creationTimeMs = int(time.time() * 1000)

        def run_retention(self, now_ms, retention_ms):
            horizon = now_ms - retention_ms
            return [s for s in self.segments if s.end_ms < horizon]
        """
        assert _rules(src) == []


class TestW025BareAxisLiteralInCollective:
    def test_flags_string_literal_axis_in_psum(self):
        src = """
        from jax import lax

        def combine(x):
            return lax.psum(x, "seg")
        """
        assert _rules(src) == ["W025"]

    def test_flags_tuple_literal_axes_in_all_gather(self):
        src = """
        from jax import lax

        def fetch(v):
            return lax.all_gather(v, ("replica", "shard"), tiled=True)
        """
        assert _rules(src) == ["W025"]

    def test_flags_axis_name_keyword_on_jax_lax_call(self):
        src = """
        import jax

        def exchange(buf):
            return jax.lax.all_to_all(
                buf, axis_name="shard", split_axis=0, concat_axis=0
            )
        """
        assert _rules(src) == ["W025"]

    def test_flags_axis_index_literal(self):
        src = """
        from jax import lax

        def my_device():
            return lax.axis_index("replica")
        """
        assert _rules(src) == ["W025"]

    def test_quiet_on_threaded_axis_variable(self):
        src = """
        from jax import lax

        def combine(x, axis):
            return lax.psum(x, axis)
        """
        assert _rules(src) == []

    def test_quiet_on_mesh_module_constants(self):
        src = """
        from jax import lax
        from pinot_tpu.parallel import mesh as mesh_mod

        def combine(x):
            return lax.psum(x, mesh_mod.SEG_AXIS)
        """
        assert _rules(src) == []

    def test_quiet_on_non_axis_string_and_non_collective_calls(self):
        # a cache-group key tuple containing "seg" is NOT a collective arg
        # (segment/segment.py keys caches this way) and psum on some other
        # object is not a mesh collective
        src = """
        def key_for(self, device):
            return ("seg", id(self), device)

        def reduce_with(engine, x):
            return engine.psum(x, "seg")
        """
        assert _rules(src) == []

    def test_exempt_inside_parallel_mesh(self):
        src = """
        from jax import lax

        def psum_hierarchical(x):
            return lax.psum(x, "shard")
        """
        out = lint_source(textwrap.dedent(src), path="pinot_tpu/parallel/mesh.py")
        assert out == []


class TestW026ControllerDiscipline:
    def test_flags_direct_knob_write_outside_setter(self):
        # runtime knob mutation skipping the clamped registry setter
        src = """
        class Adaptor:
            def react(self, hc):
                hc.budget_pct = 60.0
        """
        assert _rules(src) == ["W026"]

    def test_flags_augassign_on_managed_knob(self):
        src = """
        def narrow(engine):
            engine.pipeline_depth -= 1
        """
        assert _rules(src) == ["W026"]

    def test_flags_wall_clock_inside_autopilot_module(self):
        src = """
        import time

        class Autopilot:
            def tick(self):
                return time.monotonic()
        """
        out = lint_source(textwrap.dedent(src), path="cluster/autopilot.py")
        assert [f.rule for f in out] == ["W026"]

    def test_quiet_on_init_wiring_and_property_setter(self):
        # construction wires defaults; the property setter IS the sanctioned
        # pin-the-override path (stores an underscore override)
        src = """
        class HedgeController:
            def __init__(self, budget_pct):
                self.budget_pct = budget_pct

            @budget_pct.setter
            def budget_pct(self, value):
                self._budget_pct_override = float(value)
        """
        assert _rules(src) == []

    def test_quiet_on_injected_clock_in_autopilot_module(self):
        # threads.monotonic is the injection seam, self.clock() the fake —
        # neither is the wall clock
        src = """
        from pinot_tpu.utils import threads

        class Autopilot:
            def tick(self):
                return self.clock() + threads.monotonic()
        """
        out = lint_source(textwrap.dedent(src), path="cluster/autopilot.py")
        assert out == []

    def test_quiet_on_wall_clock_outside_autopilot_module(self):
        # the wall-clock half of W026 is scoped to autopilot modules (other
        # wall-clock misuse belongs to W005/W017/W022)
        src = """
        import time

        def stamp():
            return time.monotonic()
        """
        assert _rules(src) == []


def test_syntax_error_is_a_finding_not_a_crash():
    out = lint_source("def broken(:\n", path="x.py")
    assert len(out) == 1 and out[0].rule == "E000"


def test_finding_str_is_greppable():
    f = Finding("a/b.py", 12, "W001", "msg")
    assert str(f) == "a/b.py:12: W001 msg"


def test_live_tree_is_clean():
    """The shipped package must lint clean — this is the CI gate that keeps
    the broker-race class of bug from regressing."""
    findings = lint_tree()
    assert findings == [], "\n".join(str(f) for f in findings)
