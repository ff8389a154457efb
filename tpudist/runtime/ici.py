"""Resizable XLA-collective data plane for elastic training.

Round 3 left one place where the framework wasn't TPU-first: after a
TTL-detected resize, :mod:`tpudist.elastic.worker` synced gradients through
:class:`~tpudist.runtime.collectives.HostCollectives` — the store that is
supposed to be control-plane-only (``native/coord.cpp``'s own contract, and
the role split the reference itself draws at
`server_model_data_parallel.py:119-122`: RPC control on :29501 vs gloo data
on :29500).  This module moves the post-resize data plane onto XLA
collectives: after every rendezvous round, the gang bootstraps a fresh
``jax.distributed`` world sized to the round, and gradient sync runs as a
compiled ``jax.lax.pmean`` over a ``Mesh`` spanning the member processes —
ICI/DCN on TPU pods, gloo TCP on the CPU backend used by the tests.

How an in-process RESIZE of a compiled-collective world works:

1. every device value that must survive is snapshotted to host numpy
   (:func:`host_snapshot` — ``clear_backends`` invalidates every
   ``jax.Array``, and typed PRNG keys additionally need their impl
   recorded to round-trip);
2. the previous distributed runtime is torn down: the coordination
   client disconnects, jax's distributed global state is reset, then
   ``jax.extend.backend.clear_backends()`` drops the backend and every
   jit cache (nothing may hold a stale executable across the swap);
3. the round's rank 0 spawns a fresh coordination service in its OWN
   detached process (:mod:`tpudist.runtime.ici_service` — a worker-
   hosted leader is fatal to elasticity: a coordination client whose
   leader becomes unreachable ``LOG(FATAL)``s its process) and publishes
   the address under ``{ns}/{round}/addr`` in the coord store (control
   plane); everyone connects at the new size and the new backend's
   devices form the data mesh.

Failure detection is symmetric by construction: collectives are
dispatched asynchronously and POLLED (:meth:`IciCollectives._wait_ready`)
with the TTL membership probe in between, so a member death surfaces as
``WorldChanged``/a collective error on every survivor within one TTL —
whatever its position in the gloo ring — measured end-to-end in the
kill -9 tests (`tests/test_elastic_ici.py`).

On real TPU pods the device plane cannot be re-sized in-process (device
ownership is fixed at runtime startup); there the same rendezvous drives
the gang-restart path (``runtime/launch.py --max-restarts``) and this
module's ``initialize`` runs once per process lifetime with the TPU
defaults.  The in-process resize is exercised on the CPU backend, which is
also where the reference's elastic examples run their own data plane
(gloo, `mnist_ddp_elastic.py:26`).
"""

from __future__ import annotations

import os
import socket
import time
from typing import Any, Callable

import numpy as np

from tpudist.runtime.coord import CoordClient
from tpudist.utils.logging import get_logger

log = get_logger(__name__)

# message fragments that identify a failed XLA/gloo collective or a dead
# distributed runtime — the ICI analog of HostCollectives' PeerLost
_COLLECTIVE_FAILURE_MARKS = (
    "gloo",                    # "Gloo all-reduce failed: ..."
    "connection reset",
    "connection refused",
    "coordination service",
    "deadline exceeded",
    "barrier timed out",
    "socket closed",
    "distributed runtime",
)


class FormationTimeout(RuntimeError):
    """The round's distributed world never formed (rank 0 vanished before
    publishing, or a peer died inside the connection barrier)."""


def is_collective_failure(exc: BaseException) -> bool:
    """Does this exception look like a peer-loss inside the compiled data
    plane (rather than a bug)?  Matched on the message because XLA surfaces
    gloo/coordination failures as plain ``ValueError``/``RuntimeError`` —
    and ONLY on those types, so an unrelated exception whose message
    happens to contain e.g. "socket closed" is not silently treated as a
    membership change.  ``ConnectionError`` is excluded explicitly even
    though it is not a RuntimeError/ValueError: the coord-store client
    raises it, and a control-plane outage must propagate, not trigger
    re-rendezvous against a dead store."""
    if isinstance(exc, ConnectionError):
        return False
    if not isinstance(exc, (RuntimeError, ValueError)):
        return False
    msg = str(exc).lower()
    return any(mark in msg for mark in _COLLECTIVE_FAILURE_MARKS)


def host_snapshot(tree: Any) -> tuple[Any, Callable[[], Any]]:
    """Snapshot ``tree`` to host numpy and return ``(host_tree, restore)``.

    ``restore()`` rebuilds the tree on whatever backend is current when it
    runs — the backend-swap helper: raw numpy survives
    ``clear_backends()``; typed PRNG keys are re-wrapped from their
    recorded impl (a plain spec object, backend-independent)."""
    import jax

    from tpudist.utils.trees import is_prng_key, tree_to_numpy

    impls = jax.tree.map(
        lambda leaf: jax.random.key_impl(leaf) if is_prng_key(leaf)
        else False, tree)
    host = tree_to_numpy(tree)

    def restore() -> Any:
        import jax.numpy as jnp

        return jax.tree.map(
            lambda h, impl: (jax.random.wrap_key_data(jnp.asarray(h),
                                                      impl=impl)
                             if impl is not False else h),
            host, impls)

    return host, restore


# retired distributed-runtime handles (see IciDataPlane.teardown): kept
# alive on purpose so their destructors never fire a disconnect RPC at a
# dead/retired leader
_GRAVEYARD: list = []

from tpudist.runtime.launch import _free_port  # noqa: E402 - one probe, shared


class IciDataPlane:
    """Per-round ``jax.distributed`` world manager for the elastic worker.

    One instance lives for the whole worker; :meth:`form` is called once
    per rendezvous round and returns the round's data mesh.  The coord
    store carries ONLY the address agreement (control plane); every
    gradient byte of the formed round rides XLA collectives.

    Args:
      client: coord-store connection (main-thread use only).
      namespace: store key prefix for the address agreement.
      host_ip: address peers can reach THIS process's coordinator on when
        it is rank 0.  Default loopback (single-host tests); multi-host
        launches set ``TPUDIST_HOST_IP``.
      heartbeat_timeout_s / init_timeout_s: forwarded to
        ``jax.distributed.initialize``; init failures (a peer died between
        rendezvous and formation) surface as catchable errors within
        ``init_timeout_s``.  The heartbeat timeout defaults to a day:
        liveness detection belongs to the TTL store (seconds, not the
        coordination service's 100 s), and a parked world's client must
        never reach its missed-heartbeat handler.
    """

    def __init__(
        self,
        client: CoordClient,
        namespace: str = "ici",
        host_ip: str | None = None,
        heartbeat_timeout_s: int = 86400,
        init_timeout_s: int = 30,
    ) -> None:
        self.client = client
        self.ns = namespace
        self.host_ip = (host_ip or os.environ.get("TPUDIST_HOST_IP")
                        or "127.0.0.1")
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.init_timeout_s = init_timeout_s
        self._active_round: int | None = None

    # -- lifecycle ---------------------------------------------------------

    def form(
        self,
        round_id: int,
        rank: int,
        world: int,
        on_wait: Callable[[], None] | None = None,
    ) -> Any:
        """Bootstrap the round's ``jax.distributed`` world; returns its
        1-axis data :class:`jax.sharding.Mesh` (axis ``"data"``, one entry
        per member process's devices).

        MUST be called with every to-survive value already host-resident
        (:func:`host_snapshot`): the previous backend — including the
        single-process one used during model init — is torn down here.

        Raises on formation failure (address-agreement timeout, a peer
        dying mid-init); callers treat that like any other membership
        change and re-rendezvous."""
        import jax
        from jax._src import distributed as jdist
        from jax._src.lib import _jax as _jaxlib

        self.teardown()
        addr = self._agree_address(round_id, rank, world, on_wait)
        log.info("ici round %d: initialize rank %d/%d at %s",
                 round_id, rank, world, addr)
        # The coordination client is built directly (not via
        # ``jax.distributed.initialize``): the service lives in its OWN
        # spawned process (see :mod:`tpudist.runtime.ici_service` for why
        # a worker-hosted leader is fatal to elasticity), and the client
        # must never fire a disconnect RPC from a destructor.
        client = _jaxlib.get_distributed_runtime_client(
            addr, rank,
            init_timeout=self.init_timeout_s,
            heartbeat_timeout=self.heartbeat_timeout_s,
            shutdown_on_destruction=False,
            use_compression=True,
            recoverable=True)
        client.connect()
        jdist.global_state.client = client
        jdist.global_state.process_id = rank
        jdist.global_state.num_processes = world
        jdist.global_state.coordinator_address = addr
        self._active_round = round_id
        devices = jax.devices()
        if len(devices) % world != 0:
            raise RuntimeError(
                f"ici round {round_id}: {len(devices)} devices not "
                f"divisible by world {world}")
        return jax.sharding.Mesh(np.asarray(devices), ("data",))

    def teardown(self) -> None:
        """Retire the current distributed world plus hard-reset jax's
        backend/jit caches.  Idempotent; safe at any point of the world's
        lifecycle, including with peers already dead.

        The disconnect is CLEAN even after member deaths because the
        service this client talks to lives in its own process
        (:mod:`tpudist.runtime.ici_service`), not in any worker — there
        is no "leader died" case.  Should the disconnect still fail
        (e.g. the service was swept by a much newer round), the client is
        parked in a module graveyard so its destructor never retries the
        RPC."""
        from jax._src import distributed as jdist

        client = jdist.global_state.client
        if client is not None:
            try:
                client.shutdown()
            except Exception as e:  # noqa: BLE001 - teardown must proceed
                log.warning("ici teardown: disconnect failed (%s); parking",
                            str(e)[:200])
                _GRAVEYARD.append(client)
        if jdist.global_state.preemption_sync_manager is not None:
            _GRAVEYARD.append(jdist.global_state.preemption_sync_manager)
        jdist.global_state.client = None
        jdist.global_state.service = None
        jdist.global_state.preemption_sync_manager = None
        from jax.extend.backend import clear_backends

        clear_backends()
        # The PJRT client (and with it the gloo TCP pairs) is freed only
        # when its LAST reference dies, and a blocked peer of a half-dead
        # world only unblocks when those sockets close — the unblock
        # latency IS the gang's re-rendezvous latency.  clear_backends
        # drops the backend registry and jit caches, but jax ALSO interns
        # every Mesh in a global dict keyed by its device tuple
        # (jax._src.mesh._mesh_object_dict), which pins the dead client's
        # Device objects forever; purge it (meshes re-intern on demand)
        # and collect now rather than whenever the GC next runs.
        from jax._src import mesh as jmesh

        jmesh._mesh_object_dict.clear()
        import gc

        gc.collect()
        self._active_round = None

    def finalize(self, rank: int, barrier: Callable[[], None]) -> None:
        """End-of-run cleanup: disconnect, synchronize so every member has
        disconnected, then let rank 0 reap every service process this
        plane ever spawned (same-host reach; remote leftovers self-expire
        via ``--max-lifetime-s``)."""
        self.teardown()
        barrier()
        if rank == 0:
            self._sweep(upto=None)

    # -- service spawning + address agreement (control plane) --------------

    def _agree_address(self, round_id: int, rank: int, world: int,
                       on_wait: Callable[[], None] | None) -> str:
        key = f"{self.ns}/{round_id}/addr"
        if rank == 0:
            port = self._spawn_service(round_id, world)
            addr = f"{self.host_ip}:{port}"
            self.client.set(key, addr)
            # Reap services ≥ 2 generations stale: every member of the
            # CURRENT round has (by registering) already finished tearing
            # down round-1's world, so nothing can still be disconnecting
            # from a round-2 service.  Sweeping round-1 here could race a
            # laggard's clean disconnect.
            self._sweep(upto=round_id - 2)
            return addr
        deadline = time.monotonic() + self.init_timeout_s
        while True:
            raw = self.client.get(key)
            if raw is not None:
                return raw.decode()
            if on_wait is not None:
                on_wait()
            if time.monotonic() > deadline:
                raise FormationTimeout(
                    f"rank 0 never published {key} within "
                    f"{self.init_timeout_s}s")
            self.client.wait(key, timeout_s=0.2)

    def _spawn_service(self, round_id: int, world: int) -> int:
        """Launch this round's coordination service in its own process and
        return its port; publishes ``{ns}/{round}/svc`` = ``pid:host`` for
        the generational sweep."""
        import select
        import subprocess
        import sys

        import tempfile

        port = _free_port()
        # stderr to its own log file: the daemon outlives this worker, so
        # inheriting a harness's stderr PIPE would hold its write end open
        # (the harness's read-to-EOF then blocks on the daemon's lifetime)
        errlog_path = os.path.join(
            tempfile.gettempdir(), f"tpudist_ici_service_{port}.log")
        errlog = open(errlog_path, "wb")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "tpudist.runtime.ici_service",
                 "--port", str(port), "--world", str(world),
                 "--heartbeat-timeout-s", str(self.heartbeat_timeout_s)],
                stdout=subprocess.PIPE, stderr=errlog,
                start_new_session=True)  # detach: must outlive this worker
        finally:
            errlog.close()
        ready, _, _ = select.select([proc.stdout], [], [],
                                    self.init_timeout_s)
        if not ready or proc.stdout.readline().strip() != b"ready":
            proc.kill()
            # FormationTimeout: the worker loop treats this like any other
            # membership change and re-rendezvouses (a port-bind race or a
            # slow host must not crash the gang member)
            raise FormationTimeout(
                f"ici round {round_id}: service process never came up "
                f"(its stderr is in {errlog_path})")
        proc.stdout.close()
        self.client.set(f"{self.ns}/{round_id}/svc",
                        f"{proc.pid}:{socket.gethostname()}")
        return port

    def _sweep(self, upto: int | None) -> None:
        """SIGTERM service processes of rounds ≤ ``upto`` (all when None)
        and drop their store keys.  Only same-host pids are reachable;
        others are left to their ``--max-lifetime-s`` backstop."""
        me = socket.gethostname()
        for key in self.client.keys(f"{self.ns}/"):
            parts = key.split("/")
            if len(parts) != 3 or parts[2] not in ("svc", "addr"):
                continue
            try:
                r = int(parts[1])
            except ValueError:
                continue
            if upto is not None and r > upto:
                continue
            if parts[2] == "svc":
                raw = self.client.get(key)
                if raw is not None:
                    pid_s, _, host = raw.decode().partition(":")
                    if host == me:
                        try:
                            os.kill(int(pid_s), 15)
                        except (OSError, ValueError):
                            pass
            try:
                self.client.delete(key)
            except ConnectionError:
                return


class IciCollectives:
    """Gradient-sync collectives over the compiled XLA path — the drop-in
    data-plane replacement for :class:`HostCollectives.allreduce_mean`
    (same pytree-in/pytree-out API, so a train loop swaps planes without
    changing shape).

    Each call builds (once per tree structure, AOT-cached) a jitted
    ``shard_map`` whose body is ``jax.lax.pmean`` over the mesh's data
    axis, stacks every member's contribution along that axis, and returns
    this process's (averaged) row.  ``last_hlo`` holds the compiled HLO of
    the most recent executable — the proof that gradients ride
    ``all-reduce``, asserted by the elastic ICI tests."""

    def __init__(self, mesh: Any,
                 on_check: Callable[[], None] | None = None,
                 timeout_s: float = 60.0) -> None:
        import jax

        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.world = int(np.prod(list(mesh.shape.values())))
        # processes contribute one tree each, replicated across their own
        # devices (the TPU topology: one process per host, several chips)
        me = jax.process_index()
        self.local_rows = sum(
            1 for d in mesh.devices.flat if d.process_index == me)
        self.num_processes = jax.process_count()
        # _stack_local contributes one row per LOCAL DEVICE and the pmean
        # averages over device rows; with heterogeneous per-process device
        # counts that would be a device-weighted mean, not the per-process
        # mean allreduce_mean promises (and allreduce_sum = mean × procs
        # would be silently wrong).  Fail loudly at formation instead —
        # counting EVERY process's rows (mesh.devices is global), so the
        # failure is symmetric: no member proceeds into a collective its
        # peers refused to join.
        from collections import Counter

        per_proc = Counter(d.process_index for d in mesh.devices.flat)
        if len(set(per_proc.values())) > 1:
            raise RuntimeError(
                f"IciCollectives requires a uniform device count per "
                f"process; mesh devices per process: {dict(per_proc)}")
        self._sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(self.axis))
        self._execs: dict[Any, Any] = {}
        self.on_check = on_check
        self.timeout_s = timeout_s
        self.last_hlo: str | None = None

    def release(self) -> None:
        """Drop every reference into the backend — compiled executables,
        mesh, sharding (each pins the client via its Device objects; a
        dead round's client must actually be freed so its sockets close —
        see :meth:`IciDataPlane.teardown`).  The object is unusable
        afterwards."""
        self._execs.clear()
        self.mesh = None
        self._sharding = None

    def _tree_pmean(self, tree: Any) -> Any:
        import jax

        return jax.tree.map(
            lambda x: jax.lax.pmean(x, self.axis), tree)

    def _stack_local(self, tree: Any) -> Any:
        """Each process contributes its local tree as one row PER LOCAL
        DEVICE of a global ``[world_devices, ...]`` array sharded along
        the data axis (uniform replication keeps the mean exact)."""
        import jax

        def put(leaf):
            leaf = np.asarray(leaf)
            local = np.repeat(leaf[None], self.local_rows, axis=0)
            return jax.make_array_from_process_local_data(
                self._sharding, local, (self.world, *leaf.shape))

        return jax.tree.map(put, tree)

    def _executable(self, global_tree: Any) -> Any:
        import jax

        key = jax.tree.structure(global_tree), tuple(
            (leaf.shape, str(leaf.dtype))
            for leaf in jax.tree.leaves(global_tree))
        exe = self._execs.get(key)
        if exe is None:
            from tpudist.obs.xla import compile_watch

            spec = jax.sharding.PartitionSpec(self.axis)
            fn = jax.jit(jax.shard_map(
                self._tree_pmean, mesh=self.mesh,
                in_specs=spec, out_specs=spec))
            with compile_watch("ici"):
                exe = fn.lower(global_tree).compile()
            self._execs[key] = exe
            # rendered once per compile (the text is identical for a
            # cache hit and re-rendering a large module every step isn't)
            self.last_hlo = exe.as_text()
            try:
                from tpudist import obs

                obs.recorder.note_hlo(self.last_hlo)
            except Exception:  # noqa: BLE001 - telemetry is best-effort
                pass
        return exe

    def allreduce_mean(self, tree: Any) -> Any:
        """Mean-reduce a pytree across the mesh's member processes through
        one compiled all-reduce; returns host numpy (the elastic loop
        commits host-side)."""
        if self.on_check is not None:
            # membership probe BEFORE entering the collective: a peer the
            # TTL already declared dead would leave us stuck on an op
            # that can never complete
            self.on_check()
        global_tree = self._stack_local(tree)
        out = self._executable(global_tree)(global_tree)
        self._wait_ready(out)
        return self._local_row(out)

    def _wait_ready(self, tree: Any) -> None:
        """Poll the dispatched collective's buffers instead of blocking on
        them.  Load-bearing for detection SYMMETRY: when a member dies
        mid-collective, only its gloo-ring neighbor gets an instant
        connection-reset — a non-adjacent survivor's op simply never
        completes, and a thread blocked inside gloo cannot be interrupted.
        Dispatch is async (the CPU client delivers failures through buffer
        definition events), so the main thread polls ``is_ready`` with the
        TTL probe in between: every survivor surfaces the death as
        ``WorldChanged`` within one TTL, whatever its ring position.  The
        abandoned op stays pending inside the dead world's client, which
        is leaked by design — joining its execute thread would block
        forever (one dangling client per resize, bounded by
        ``max_rounds``)."""
        import time as _time

        import jax

        pending = list(jax.tree.leaves(tree))
        deadline = _time.monotonic() + self.timeout_s
        # the readiness poll is 2 ms, but the membership probe is a coord-
        # store RPC — rate-limit it so a long collective doesn't hammer
        # the control plane (one live() per ~100 ms is far inside the TTL)
        next_check = 0.0
        while True:
            pending = [leaf for leaf in pending if not leaf.is_ready()]
            if not pending:
                return
            now = _time.monotonic()
            if self.on_check is not None and now >= next_check:
                self.on_check()
                next_check = now + 0.1
            if _time.monotonic() > deadline:
                from tpudist.runtime.collectives import PeerLost

                raise PeerLost(
                    f"ici collective not ready within {self.timeout_s}s "
                    f"at world {self.world}")
            _time.sleep(0.002)

    def allreduce_sum(self, tree: Any) -> Any:
        mean = self.allreduce_mean(tree)
        import jax

        return jax.tree.map(lambda x: x * self.num_processes, mean)

    # -- reduce-scatter / all-gather primitives ------------------------------
    # True primitives (jax.lax.psum_scatter / all_gather inside the same
    # compiled shard_map shell as the pmean) — NOT all-gather-and-reduce.
    # They are the intra-host legs of HostCollectives' algorithm="hier"
    # (see IciIntraHost below): each member process holds one flat
    # vector; reduce_scatter leaves it with its shard of the sum, the
    # cross-host ring reduces that shard over the store, and all_gather
    # rebuilds the full vector from the finished shards.

    def rs_bounds(self, n: int) -> list[tuple[int, int]]:
        """Per-PROCESS shard boundaries used by :meth:`reduce_scatter` /
        :meth:`all_gather` for a vector of ``n`` elements: equal padded
        shards of ``ceil(n/world_devices) × local_rows`` elements
        (psum_scatter tiles per DEVICE, so the per-process shard must be
        a whole number of device tiles), clamped to ``[0, n)``.
        Identical on every member — the hier dispatcher uses these as
        the shard map."""
        q = -(-n // self.world) if n else 0
        per = q * self.local_rows
        return [(min(n, p * per), min(n, (p + 1) * per))
                for p in range(self.num_processes)]

    def _rs_executable(self, kind: str, shape: tuple, dtype: Any) -> Any:
        import jax

        key = (kind, shape, str(dtype))
        exe = self._execs.get(key)
        if exe is None:
            from tpudist.obs.xla import compile_watch

            spec = jax.sharding.PartitionSpec(self.axis)
            if kind == "rs":
                def body(x):  # per device (1, npad) -> (1, q)
                    return jax.lax.psum_scatter(
                        x, self.axis, scatter_dimension=1, tiled=True)

                out_spec = spec
            else:
                def body(x):  # per device (1, q) -> replicated (world, q)
                    return jax.lax.all_gather(
                        x, self.axis, axis=0, tiled=True)

                out_spec = jax.sharding.PartitionSpec()
            # check_vma can't statically infer that a tiled all-gather's
            # output is replicated; the gather itself guarantees it
            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=spec, out_specs=out_spec,
                check_vma=(kind == "rs")))
            arg = jax.ShapeDtypeStruct(shape, dtype, sharding=self._sharding)
            with compile_watch("ici"):
                exe = fn.lower(arg).compile()
            self._execs[key] = exe
            self.last_hlo = exe.as_text()
        return exe

    def _local_rows_of(self, out: Any) -> list[np.ndarray]:
        """This process's device rows of a dim-0-sharded array, in global
        row order, asserted contiguous (the formed mesh orders devices by
        process, which is what makes a process's shard one contiguous
        slice)."""
        rows = sorted(
            ((s.index[0].start or 0, np.asarray(s.data))
             for s in out.addressable_shards),
            key=lambda t: t[0])
        starts = [r for r, _ in rows]
        if starts != list(range(starts[0], starts[0] + len(starts))):
            raise RuntimeError(
                f"process device rows not contiguous in mesh: {starts}")
        return [d.reshape(-1) for _, d in rows]

    def reduce_scatter(self, vec: np.ndarray) -> np.ndarray:
        """SUM-reduce an identical-length 1-D vector across member
        processes and return only this process's :meth:`rs_bounds` shard
        — one compiled ``reduce-scatter``, moving ``(world-1)/world`` of
        the bytes an all-reduce would.  Exactness: only each process's
        FIRST device row carries the payload (the rest contribute
        zeros), so the device-level sum equals the process-level sum
        with no replication scaling to divide away."""
        import jax

        if self.on_check is not None:
            self.on_check()
        vec = np.asarray(vec)
        n = vec.size
        lo, hi = self.rs_bounds(n)[jax.process_index()]
        if n == 0:
            return np.empty(0, vec.dtype)
        q = -(-n // self.world)
        npad = q * self.world
        local = np.zeros((self.local_rows, npad), dtype=vec.dtype)
        local[0, :n] = vec
        garr = jax.make_array_from_process_local_data(
            self._sharding, local, (self.world, npad))
        out = self._rs_executable("rs", (self.world, npad), vec.dtype)(garr)
        self._wait_ready(out)
        full = np.concatenate(self._local_rows_of(out))
        return full[:hi - lo]

    def all_gather(self, shard: np.ndarray, n: int) -> np.ndarray:
        """Inverse of :meth:`reduce_scatter`: every process contributes
        its ``rs_bounds`` shard of a length-``n`` vector and receives the
        whole vector — one compiled ``all-gather``, no arithmetic, so
        the result is bitwise the concatenation of the posted shards on
        every member."""
        import jax

        if self.on_check is not None:
            self.on_check()
        shard = np.asarray(shard)
        bounds = self.rs_bounds(n)
        lo, hi = bounds[jax.process_index()]
        if shard.size != hi - lo:
            raise ValueError(
                f"shard has {shard.size} elements; rs_bounds expects "
                f"{hi - lo} for process {jax.process_index()} of n={n}")
        if n == 0:
            return np.empty(0, shard.dtype)
        q = -(-n // self.world)
        per = q * self.local_rows
        buf = np.zeros(per, dtype=shard.dtype)
        buf[:shard.size] = shard
        local = buf.reshape(self.local_rows, q)
        garr = jax.make_array_from_process_local_data(
            self._sharding, local, (self.world, q))
        out = self._rs_executable("ag", (self.world, q), shard.dtype)(garr)
        self._wait_ready(out)
        flat = np.asarray(out.addressable_shards[0].data) \
            .reshape(self.num_processes, per)
        return np.concatenate([
            flat[p, :bounds[p][1] - bounds[p][0]]
            for p in range(self.num_processes)])

    # -- async handles ------------------------------------------------------
    # XLA dispatch is ALREADY asynchronous (the executable call returns
    # before the collective completes; _wait_ready polls afterwards), so
    # the async API here needs no worker thread: submit dispatches on the
    # caller's thread and the Handle defers only the readiness wait +
    # local-row extraction.  Same contract as
    # HostCollectives.allreduce_*_async — submit, overlap host work, wait.

    def allreduce_mean_async(self, tree: Any) -> "IciAsyncHandle":
        if self.on_check is not None:
            self.on_check()
        global_tree = self._stack_local(tree)
        out = self._executable(global_tree)(global_tree)
        return IciAsyncHandle(self, out, scale=1.0)

    def allreduce_sum_async(self, tree: Any) -> "IciAsyncHandle":
        h = self.allreduce_mean_async(tree)
        h.scale = float(self.num_processes)
        return h

    def _local_row(self, out_tree: Any) -> Any:
        import jax

        return jax.tree.map(
            lambda a: np.asarray(a.addressable_shards[0].data)[0], out_tree)


class IciAsyncHandle:
    """In-flight compiled allreduce: the op was dispatched at submit time;
    :meth:`wait` polls it ready (same TTL-probing poll as the sync path,
    so a peer death still surfaces as ``WorldChanged``/``PeerLost`` from
    ``wait()``) and returns this process's reduced row."""

    def __init__(self, coll: IciCollectives, out_tree: Any,
                 scale: float) -> None:
        self._coll = coll
        self._out = out_tree
        self.scale = scale

    def done(self) -> bool:
        import jax

        return all(leaf.is_ready() for leaf in jax.tree.leaves(self._out))

    def wait(self, timeout_s: float | None = None) -> Any:
        import jax

        self._coll._wait_ready(self._out)
        row = self._coll._local_row(self._out)
        if self.scale != 1.0:
            row = jax.tree.map(lambda x: x * self.scale, row)
        return row


class IciIntraHost:
    """Adapter presenting an :class:`IciCollectives` that spans ONE
    host's member processes as the intra-host plane of
    ``HostCollectives(config=CollectiveConfig(algorithm="hier"), intra=...)``.

    With it, hier's phase 1 and phase 3 ride the compiled ICI
    reduce-scatter/all-gather instead of the coord store, and only the
    per-shard cross-host ring touches the store — the deployment shape
    the hierarchical algorithm exists for.  Requirements checked by the
    dispatcher: the wrapped mesh's process group must be exactly this
    rank's host group (``local_world == world // hosts``, host-contiguous
    rank numbering), which is how the launcher numbers a gang.

    The protocol (duck-typed by :class:`HostCollectives`):
    ``local_world`` / ``local_index`` attributes, ``bounds(n)`` for the
    shard map, ``reduce_scatter(vec)`` → this process's shard of the
    host sum, ``all_gather(shard, n)`` → the full host vector."""

    def __init__(self, coll: IciCollectives) -> None:
        import jax

        self._coll = coll
        self.local_world = coll.num_processes
        self.local_index = jax.process_index()

    def bounds(self, n: int) -> list[tuple[int, int]]:
        return self._coll.rs_bounds(n)

    def reduce_scatter(self, vec: np.ndarray) -> np.ndarray:
        return self._coll.reduce_scatter(vec)

    def all_gather(self, shard: np.ndarray, n: int) -> np.ndarray:
        return self._coll.all_gather(shard, n)
