"""User-facing API: the ``AutoDist`` facade.

Parity: ``/root/reference/autodist/autodist.py:46-322`` — construct with a
resource spec + strategy builder, capture the user's single-device program,
build-or-load the strategy (chief builds + serializes; workers load by id),
compile it against the cluster, transform, and hand back a runnable session.

JAX shape of the same flow::

    ad = AutoDist(resource_spec_file, AllReduce(chunk_size=128))
    with ad.scope():
        params = init_params(...)                      # plain single-device code
    item = ad.capture(loss_fn, params, optax.sgd(0.1), example_batch)
    runner = ad.create_distributed_session(item)       # build/load -> compile -> transform
    state = runner.create_state()
    state, metrics = runner.step(state, batch)

or the TF2-style one-liner (parity: ``autodist.py:204-289``)::

    @ad.function(optimizer=optax.sgd(0.1))
    def train_step(params, batch): ...
    loss = train_step(params, batch)    # first call compiles; state kept inside
"""
import contextlib
import itertools

from autodist_tpu import const, observability
from autodist_tpu.cluster import Cluster
from autodist_tpu.coordinator import Coordinator
from autodist_tpu.graph_item import GraphItem
from autodist_tpu.kernel.graph_transformer import GraphTransformer
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runner import Runner
from autodist_tpu.strategy.base import Strategy, StrategyCompiler
from autodist_tpu.strategy.ps_strategy import PS
from autodist_tpu.utils import logging

_default_autodist = None

# Strategy-ship KV key sequence (see _ship_or_fetch_strategy): process-global
# so keys never repeat within one coordination-service lifetime.
_ship_counter = itertools.count(1)


def get_default_autodist():
    return _default_autodist


def _reset_default():
    """Clear the per-process singleton (test harness hook)."""
    global _default_autodist
    _default_autodist = None


class AutoDist:
    """One instance per process (parity: ``autodist.py:46-51``)."""

    def __init__(self, resource_spec_file=None, strategy_builder=None,
                 mesh_axes=None, devices=None):
        """``devices`` overrides the mesh's device list — pass a detached
        topology's devices (``jax.experimental.topologies``) to AOT-compile
        the distributed program for a pod shape that isn't attached (the
        resource spec should then describe the same topology, e.g. a
        ``tpu:`` block)."""
        global _default_autodist
        if _default_autodist is not None:
            raise NotImplementedError(
                "Only one AutoDist instance per process is supported; call "
                "autodist_tpu.autodist._reset_default() in tests")
        _default_autodist = self
        self._resource_spec = ResourceSpec(resource_spec_file)
        self._strategy_builder = self._resolve_builder(strategy_builder)
        self._mesh_axes = mesh_axes
        self._devices_override = devices
        self._cluster = Cluster(self._resource_spec)
        self._coordinator = None
        self._runner = None
        self._fn_state = None
        # Local multi-process launch ("launch: local" spec): spawn workers
        # and join the coordination service NOW, before any user code can
        # touch JAX — jax.distributed.initialize must precede backend init,
        # and capture()-time tracing may create concrete constants. The
        # strategy does not exist yet at launch; once built, the chief ships
        # it to every worker over the coordination service's KV store
        # (_ship_or_fetch_strategy), so workers load the chief's exact
        # artifact. The AUTODIST_STRATEGY_ID file contract remains for
        # platform-launched jobs with a pre-built strategy on a shared FS.
        spec = self._resource_spec
        if (spec.local_launch or spec.remote_launch) and spec.num_processes > 1:
            if self.is_chief:
                self._coordinator = Coordinator(None, self._cluster)
                self._coordinator.launch_clients()
            self._cluster.start()

    @staticmethod
    def _resolve_builder(builder):
        """Resolve the strategy policy: an explicit builder wins; else the
        ``AUTODIST_STRATEGY`` env knob ('auto' => the tuner's
        :class:`~autodist_tpu.tuner.AutoStrategy`, any builder name =>
        that builder's default config — docs/tuning.md); else PS."""
        if builder is not None:
            return builder
        name = const.ENV.AUTODIST_STRATEGY.val
        if name:
            from autodist_tpu.tuner import builder_from_name
            resolved = builder_from_name(name)
            logging.info("AUTODIST_STRATEGY=%s -> %s", name,
                         type(resolved).__name__)
            return resolved
        return PS()

    @property
    def runner(self):
        """The Runner of the last ``build`` / ``create_distributed_session``
        (None before it), so that a reader holding only
        ``get_default_autodist()`` can reach the program's step."""
        return self._runner

    @property
    def resource_spec(self):
        return self._resource_spec

    @property
    def cluster(self):
        return self._cluster

    @property
    def coordinator(self):
        """The chief's Coordinator (None on workers / before setup).
        Pass it to ``CheckpointManager.run(..., coordinator=...)`` so the
        step loop can observe worker deaths (checkpoint-and-exit) and
        elastic re-form requests (docs/elasticity.md)."""
        return self._coordinator

    @property
    def is_chief(self):
        return not const.ENV.AUTODIST_WORKER.val

    # -- capture -------------------------------------------------------------

    @contextlib.contextmanager
    def scope(self):
        """Graph-capture scope (parity: ``autodist.py:309-322``).

        JAX programs need no capture hooks — the scope exists for script
        compatibility and to mark the region whose code must be identical on
        every process.
        """
        yield self

    def capture(self, loss_fn, params, optimizer, example_batch=None, **kwargs):
        """Capture the single-device program into a GraphItem."""
        with observability.span("capture"):
            return GraphItem.capture(loss_fn, params, optimizer,
                                     example_batch=example_batch, **kwargs)

    # -- build pipeline (parity: autodist.py:100-150) ------------------------

    def _build_or_load_strategy(self, graph_item):
        sid = const.ENV.AUTODIST_STRATEGY_ID.val
        if sid:  # platform-launched worker with a shared-FS artifact
            strategy = Strategy.deserialize(sid)
            logging.info("loaded strategy %s", sid)
            return strategy
        import jax
        if jax.process_count() > 1:
            return self._ship_or_fetch_strategy(graph_item)
        return self._build_local(graph_item)

    def _build_local(self, graph_item):
        """Build with this process's builder and serialize the artifact.

        Serialization is an inspection/debugging convenience, not a
        correctness dependency — tolerate read-only working dirs (the
        logging setup makes the same allowance)."""
        strategy = self._strategy_builder.build(graph_item,
                                                self._resource_spec)
        try:
            strategy.serialize()
        except OSError as e:
            logging.warning("could not serialize strategy %s: %s",
                            strategy.id, e)
        logging.info("built strategy %s with %s", strategy.id,
                     type(self._strategy_builder).__name__)
        return strategy

    def _ship_fingerprint(self, graph_item):
        """Fingerprint of (graph_item, resource_spec): what the shipped
        strategy must have been built FOR.  Two processes whose build-call
        sequences diverge (conditional capture, chief-only rebuild) would
        otherwise agree on a counter value while meaning different
        programs — the fingerprinted key turns that silent SPMD divergence
        into a loud timeout, and the id echo check below into a loud
        mismatch error."""
        import hashlib
        h = hashlib.sha256()
        for v in graph_item.variables:
            h.update(f"{v.name}|{tuple(v.shape)}|{v.dtype}|"
                     f"{v.trainable}\n".encode())
        spec = self._resource_spec
        h.update(f"np={spec.num_processes}|mesh={sorted(spec.mesh_hints.items())}|"
                 f"builder={type(self._strategy_builder).__name__}\n".encode())
        return h.hexdigest()[:16]

    def _ship_or_fetch_strategy(self, graph_item):
        """Chief builds ONCE and ships the serialized artifact through the
        coordination service's key-value store; every worker blocks for the
        exact bytes and deserializes.

        TPU-native analog of the reference's strategy scp
        (``/root/reference/autodist/coordinator.py:84-88`` +
        ``autodist.py:100-109``): same single-build guarantee with no shared
        filesystem, and it structurally removes the builder-determinism
        requirement — an unseeded or randomized builder (e.g.
        RandomAxisPartitionAR's rng) yields one program for the whole job
        instead of silently divergent SPMD programs per process.

        Hardening (ADVICE r5): the KV client and its byte methods are jax
        *internals* — any of them missing degrades to the deterministic
        local rebuild instead of crashing startup; the key carries a
        fingerprint of (graph_item, resource_spec) so a diverged build
        sequence cannot silently hand a worker the wrong program; transient
        KV faults retry with backoff."""
        import jax
        from autodist_tpu.resilience import chaos, retry
        try:
            from jax._src import distributed as jax_distributed
            client = jax_distributed.global_state.client
        except (ImportError, AttributeError) as e:
            logging.warning("jax internals for strategy shipping unavailable "
                            "(%s); every process rebuilds the strategy "
                            "(determinism required)", e)
            return self._build_local(graph_item)
        set_bytes = getattr(client, "key_value_set_bytes", None)
        get_bytes = getattr(client, "blocking_key_value_get_bytes", None)
        if client is None or set_bytes is None or get_bytes is None:
            # multi-process without the coordination service, or a jax
            # whose KV client dropped the bytes API
            logging.warning("no coordination-service KV byte channel; every "
                            "process rebuilds the strategy (determinism "
                            "required)")
            return self._build_local(graph_item)
        # Key sequence is PROCESS-global, not per-instance: the KV store
        # lives for the jax.distributed lifetime, which spans AutoDist
        # instances (the _reset_default() flow) — a per-instance counter
        # would republish under an existing key and hand workers a stale
        # blob.  Every process runs the same script, so the sequence of
        # build calls (and hence keys) agrees across the job; the
        # fingerprint suffix catches the jobs where it doesn't.
        key = (f"autodist/strategy/{next(_ship_counter)}/"
               f"{self._ship_fingerprint(graph_item)}")
        if jax.process_index() == 0:
            strategy = self._build_local(graph_item)
            blob = strategy.proto.SerializeToString()
            with observability.span("strategy-ship", bytes=len(blob)):
                retry.retry_call(set_bytes, key, blob,
                                 describe="strategy KV publish")
                retry.retry_call(set_bytes, key + "/id",
                                 strategy.id.encode("utf-8"),
                                 describe="strategy id publish")
            if observability.enabled():
                observability.registry().gauge(
                    "strategy.ship_bytes").set(len(blob))
                observability.record_event(
                    "strategy-ship", f"published {strategy.id} "
                    f"({len(blob)} bytes)")
            logging.info("shipped strategy %s (%d bytes) to the "
                         "coordination service as %s", strategy.id,
                         len(blob), key)
        else:
            from autodist_tpu.proto import strategy_pb2
            chaos.maybe_delay_kv_fetch()
            timeout_ms = const.strategy_ship_timeout_ms()
            with observability.span("strategy-ship", side="fetch"):
                blob = retry.retry_call(get_bytes, key, timeout_ms,
                                        describe="strategy KV fetch")
            proto = strategy_pb2.Strategy()
            proto.ParseFromString(blob)
            strategy = Strategy(proto)
            # Echo check: the fetched proto must be the artifact the chief
            # published under this fingerprint (a stale republish or a
            # proto that parses by coincidence fails loudly here).
            want_id = retry.retry_call(get_bytes, key + "/id", timeout_ms,
                                       describe="strategy id fetch")
            want_id = want_id.decode("utf-8", "replace")
            if strategy.id != want_id:
                raise RuntimeError(
                    f"autodist_tpu: strategy ship mismatch under {key}: "
                    f"fetched proto id {strategy.id!r} != published id "
                    f"{want_id!r} — the chief and this worker disagree "
                    f"about the build sequence")
            ship_vars = {nc.var_name for nc in strategy.node_config}
            have_vars = {v.name for v in graph_item.trainable_variables}
            unknown = ship_vars - have_vars
            if unknown:
                raise RuntimeError(
                    f"autodist_tpu: shipped strategy {strategy.id} "
                    f"configures variables this process never captured "
                    f"({sorted(unknown)[:5]}...) — divergent SPMD programs")
            observability.record_event(
                "strategy-ship", f"fetched {strategy.id} ({len(blob)} bytes)")
            logging.info("loaded strategy %s from coordination service "
                         "(%s, %d bytes)", strategy.id, key, len(blob))
        return strategy

    def _compile_strategy(self, strategy, graph_item):
        return StrategyCompiler(graph_item, self._cluster.mesh).compile(strategy)

    def _setup(self, strategy):
        """Create the coordinator (parity: ``autodist.py:120-128``)."""
        if self.is_chief and self._coordinator is None:
            self._coordinator = Coordinator(strategy, self._cluster)

    def build(self, graph_item):
        """Full pipeline: strategy -> compile -> transform -> Runner.

        Order matters on multi-host: the cluster runtime (jax.distributed)
        starts before anything that discovers devices — strategy building
        enumerates the (global) accelerator list, and the mesh spans it.
        (For ``launch: local`` specs the workers were already spawned and
        the service joined at construction; start() is then a no-op.)
        """
        self._cluster.start()
        with observability.span("strategy-build"):
            strategy = self._build_or_load_strategy(graph_item)
        self._setup(strategy)
        mesh_axes = self._mesh_axes
        if mesh_axes is None and strategy.graph_config.mesh_axes:
            mesh_axes = dict(strategy.graph_config.mesh_axes)
        self._cluster.build_mesh(mesh_axes, devices=self._devices_override)
        with observability.span("transform"):
            compiled = self._compile_strategy(strategy, graph_item)
            program = GraphTransformer(compiled, self._cluster,
                                       graph_item).transform()
        self._runner = Runner(program)
        return self._runner

    def create_distributed_session(self, graph_item):
        """Alias keeping the reference's entry-point name
        (``autodist.py:191-198``)."""
        return self.build(graph_item)

    def build_strategy(self, graph_item):
        """Expose strategy building alone (parity: ``autodist.py:91-98``)."""
        return self._strategy_builder.build(graph_item, self._resource_spec)

    # -- TF2-style function wrapper (parity: autodist.py:204-289) ------------

    def function(self, optimizer, aux_output=False, **capture_kwargs):
        """Decorator turning a single-device loss fn into a distributed step.

        First call captures + compiles and initializes distributed state from
        the passed params; later calls ignore the params argument and step
        the internal state (session semantics). One function per instance
        (parity: ``autodist.py:281-283``).
        """
        def decorator(loss_fn):
            def run_fn(params, batch):
                if self._fn_state is None:
                    item = self.capture(loss_fn, params, optimizer,
                                        example_batch=batch,
                                        aux_output=aux_output, **capture_kwargs)
                    runner = self.build(item)
                    state = runner.create_state()
                    self._fn_state = (runner, state)
                runner, state = self._fn_state
                state, metrics = runner.step(state, batch)
                self._fn_state = (runner, state)
                return metrics
            run_fn.autodist = self
            return run_fn
        if callable(optimizer) and not hasattr(optimizer, "update"):
            raise TypeError("ad.function requires an optax optimizer: "
                            "@ad.function(optimizer=optax.sgd(...))")
        return decorator
