"""Functional communication API.

TPU-native rebuild of ``deepspeed/comm/comm.py:222-786``: the module-level
collective functions (`all_reduce`, `all_gather`, `reduce_scatter`,
`all_to_all_single`, `broadcast`, `barrier`, ...) with *groups replaced by
mesh axis names*.

Two call contexts are supported, dispatched automatically:

1. **In-trace** (inside `jit`/`shard_map` with named mesh axes): thin wrappers
   over `jax.lax` collectives — the hot path. `async_op=True` returns a
   handle whose `.wait()` is a no-op (XLA dispatch is already async).
2. **Eager** (host level, on global `jax.Array`s): implemented with
   `shard_map` over the global mesh; used for init-time broadcast, tests and
   the comms benchmark sweep. These are timed and logged by `CommsLogger`
   exactly where the reference wraps ops with ``@timed_op`` (comm.py:101).

`init_distributed` (reference comm.py:619) initializes `jax.distributed` for
multi-host when coordinator env vars are present, then builds the global mesh.
"""

import functools
import os
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import MeshContext, get_mesh_context, set_mesh_context, mesh_is_initialized, MESH_AXES
from .reduce_op import ReduceOp
from .comms_logging import get_comms_logger
from ..utils.logging import logger

AxisNames = Union[str, Sequence[str], None]

_INITIALIZED = False


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _norm_group(group: AxisNames):
    """None = the full data-parallel world (all axes)."""
    if group is None:
        return tuple(get_mesh_context().axis_names)
    if isinstance(group, str):
        return (group, )
    return tuple(group)


def _in_trace(x) -> bool:
    return isinstance(x, jax.core.Tracer)


class CommHandle:
    """Async handle with reference `.wait()` semantics; XLA dispatch is
    already asynchronous so wait() only blocks when `sync` requested."""

    def __init__(self, value=None):
        self.value = value

    def wait(self, sync=False):
        if sync and self.value is not None:
            jax.block_until_ready(self.value)
        return self.value


def timed_op(func):
    """Eager-path analog of reference ``comm.py:101 timed_op``."""
    import inspect
    sig = inspect.signature(func)

    @functools.wraps(func)
    def wrapper(tensor, *args, **kwargs):
        cl = get_comms_logger()
        do_log = cl.enabled and not _in_trace(tensor)
        if do_log:
            jax.block_until_ready(tensor)
            t0 = time.time()
        result = func(tensor, *args, **kwargs)
        if do_log:
            out = result.value if isinstance(result, CommHandle) else result
            jax.block_until_ready(out)
            dt = time.time() - t0
            bound = sig.bind(tensor, *args, **kwargs)
            group = bound.arguments.get("group", None)
            n = get_world_size(group)
            size = tensor.size * tensor.dtype.itemsize
            cl.append(func.__name__, kwargs.get("log_name", func.__name__), dt, size, n)
        return result

    return wrapper


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def parse_slurm_nodelist(nodelist: str) -> list:
    """Expand Slurm's compact nodelist syntax ("n[001-003,007],login-0",
    bracket groups may carry suffixes or repeat: "rack[1-2]-n[1-4]") into
    hostnames, without shelling out to ``scontrol show hostnames``."""

    def _split_top(s):
        parts, depth, cur = [], 0, []
        for ch in s:
            if ch == "[":
                depth += 1
            elif ch == "]":
                depth -= 1
            if ch == "," and depth == 0:
                parts.append("".join(cur))
                cur = []
            else:
                cur.append(ch)
        if cur:
            parts.append("".join(cur))
        return parts

    def _expand(tok):
        i = tok.find("[")
        if i < 0:
            return [tok]
        j = tok.index("]", i)
        prefix, body, rest = tok[:i], tok[i + 1:j], tok[j + 1:]
        vals = []
        for part in body.split(","):
            if "-" in part:
                lo, hi = part.split("-")
                width = len(lo)
                vals.extend(f"{v:0{width}d}" for v in range(int(lo), int(hi) + 1))
            else:
                vals.append(part)
        return [prefix + v + tail for v in vals for tail in _expand(rest)]

    return [h for tok in _split_top(nodelist) if tok for h in _expand(tok)]


def mpi_discovery(distributed_port: int = 29500, auto: bool = True):
    """Derive ``(coordinator_address, num_processes, process_id)`` from the
    scheduler environment — the rendezvous analog of reference
    ``comm/comm.py:688 mpi_discovery`` (which allgathers rank 0's hostname
    over mpi4py; here the coordinator is read from the launcher's env
    directly, no MPI dependency).

    Recognized environments, in priority order:
    - explicit: ``JAX_COORDINATOR_ADDRESS`` / ``COORDINATOR_ADDRESS`` +
      ``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID`` (what ``bin/deepspeed``'s ssh
      fan-out exports)
    - OpenMPI (``mpirun``): ``OMPI_COMM_WORLD_SIZE/RANK``; coordinator from
      ``OMPI_MCA_orte_hnp_uri`` ("...;tcp://ip1,ip2:port" — first IP of the
      head node)
    - Slurm (``srun``): ``SLURM_NTASKS``/``SLURM_PROCID``; coordinator =
      first host of ``SLURM_STEP_NODELIST``/``SLURM_JOB_NODELIST``
    - PDSH-style: ``DS_HOSTLIST`` (comma-separated, exported identically to
      every node) — process_id = this host's position in the list

    Returns ``(None, 1, 0)`` when nothing distributed is detected. Each of
    the three fields is resolved INDEPENDENTLY: explicit env always wins,
    and whichever scheduler family is present fills only the missing pieces
    (so ``mpirun -x JAX_NUM_PROCESSES=4`` still gets its rank from
    ``OMPI_COMM_WORLD_RANK``). ``auto=False`` disables scheduler probing but
    keeps the explicit env contract.
    """

    def _env(*names, default=None):
        for n in names:
            if os.environ.get(n) not in (None, ""):
                return os.environ[n]
        return default

    coord = _env("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS")
    # mpirun's size/rank env is part of the EXPLICIT contract (the pre-probe
    # code honored it unconditionally, and reference auto_mpi_discovery=False
    # only disables the mpi4py probing, not the env) — auto gates only the
    # coordinator guessing and the Slurm/pdsh families below
    nproc = _env("JAX_NUM_PROCESSES", "NUM_PROCESSES", "OMPI_COMM_WORLD_SIZE")
    pid = _env("JAX_PROCESS_ID", "PROCESS_ID", "OMPI_COMM_WORLD_RANK")

    if auto and _env("OMPI_COMM_WORLD_SIZE"):
        if coord is None:
            uri = _env("OMPI_MCA_orte_hnp_uri", "PMIX_SERVER_URI2", default="")
            if "tcp://" in uri:
                head = uri.split("tcp://", 1)[1].split(",")[0].split(":")[0]
                coord = f"{head}:{distributed_port}"
    elif auto and _env("SLURM_STEP_NUM_TASKS"):
        # STEP-scoped vars only: srun sets SLURM_STEP_NUM_TASKS per task,
        # while a bare `sbatch`/`salloc` shell has SLURM_NTASKS (the
        # allocation) without any step — treating the allocation size as a
        # rendezvous world would block forever waiting for peers that were
        # never launched
        nproc = nproc if nproc is not None else _env("SLURM_STEP_NUM_TASKS")
        pid = pid if pid is not None else _env("SLURM_PROCID", default="0")
        if coord is None:
            nodelist = _env("SLURM_STEP_NODELIST", "SLURM_JOB_NODELIST")
            if nodelist:
                coord = f"{parse_slurm_nodelist(nodelist)[0]}:{distributed_port}"
    elif auto and (_env("MV2_COMM_WORLD_SIZE") or _env("PMI_SIZE")):
        # MPICH / Intel MPI hydra (PMI_RANK/PMI_SIZE) and MVAPICH2
        # (MV2_COMM_WORLD_RANK/SIZE) — reference multinode_runner.py
        # MPICH/IMPI/MVAPICH runners. The PMI v1 env carries no coordinator
        # address, so the launcher must pin JAX_COORDINATOR_ADDRESS (ours
        # do); without it the explicit-env requirement surfaces below.
        nproc = nproc if nproc is not None else _env("MV2_COMM_WORLD_SIZE", "PMI_SIZE")
        pid = pid if pid is not None else _env("MV2_COMM_WORLD_RANK", "PMI_RANK",
                                               default="0")
    elif auto and _env("DS_HOSTLIST"):
        import socket
        hosts = [h for h in _env("DS_HOSTLIST").split(",") if h]
        nproc = nproc if nproc is not None else str(len(hosts))
        if pid is None:
            me = socket.gethostname()
            cands = [i for i, h in enumerate(hosts)
                     if h == me or h.split(".")[0] == me.split(".")[0]]
            if not cands:
                raise RuntimeError(
                    f"DS_HOSTLIST={_env('DS_HOSTLIST')} does not contain this "
                    f"host ({me}); every node would claim process_id=0 and "
                    "the rendezvous would hang. Use hostnames matching "
                    "`hostname` output in the hostfile, or export "
                    "JAX_PROCESS_ID explicitly.")
            pid = str(cands[0])
        if coord is None:
            coord = f"{hosts[0]}:{distributed_port}"

    return coord, int(nproc or "1"), int(pid or "0")


# rendezvous guard rails: a slow-to-arrive host should surface as bounded
# retries + a clear error, never an indefinite hang (env-overridable so an
# operator can widen the window for giant pods without a code change)
DIST_INIT_TIMEOUT_SECS = float(os.environ.get("DS_DIST_INIT_TIMEOUT", 300))
DIST_INIT_RETRIES = int(os.environ.get("DS_DIST_INIT_RETRIES", 3))
DIST_INIT_BACKOFF_SECS = float(os.environ.get("DS_DIST_INIT_BACKOFF", 1.0))


def _initialize_distributed_guarded(coord, nproc, pid, timeout=None):
    """``jax.distributed.initialize`` with bounded retry + timeout.

    The bare call blocks until every process reaches the coordinator — a
    wedged peer hangs the whole pod forever. Here each attempt carries JAX's
    ``initialization_timeout`` (when the installed version supports it) and
    transient failures retry with backoff; exhaustion raises
    ``RetriesExhausted`` so the scheduler can reschedule the job instead of
    leaking a hung allocation."""
    import inspect
    from ..utils.retry import retry_with_backoff
    from ..utils.fault_injection import get_fault_injector, InjectedFault

    if timeout is None:
        timeout = DIST_INIT_TIMEOUT_SECS
    elif hasattr(timeout, "total_seconds"):  # torch-style timedelta
        timeout = timeout.total_seconds()
    kwargs = dict(coordinator_address=coord, num_processes=nproc, process_id=pid)
    try:
        sig = inspect.signature(jax.distributed.initialize)
        if "initialization_timeout" in sig.parameters:
            kwargs["initialization_timeout"] = int(timeout)
    except (TypeError, ValueError):  # pragma: no cover — builtin/no signature
        pass

    def _attempt():
        if get_fault_injector().fire("comm.init_timeout",
                                     coordinator=coord) is not None:
            raise InjectedFault(
                f"comm.init_timeout: rendezvous with {coord} timed out")
        jax.distributed.initialize(**kwargs)

    retry_with_backoff(
        _attempt, retries=DIST_INIT_RETRIES, base_delay=DIST_INIT_BACKOFF_SECS,
        max_delay=30.0,
        exceptions=(InjectedFault, RuntimeError, OSError, TimeoutError),
        desc=f"jax.distributed.initialize({coord})")


def exchange_host_state(payload, timeout: Optional[float] = None):
    """All-gather a small pickleable host payload across processes, with a
    timeout guard: one wedged peer raises ``TimeoutError`` here instead of
    hanging the exchange forever. Returns ``[payload_0, ..., payload_{n-1}]``
    (single-process: ``[payload]`` immediately)."""
    if jax.process_count() == 1:
        return [payload]
    import pickle
    import concurrent.futures
    from jax.experimental import multihost_utils

    if timeout is None:
        timeout = DIST_INIT_TIMEOUT_SECS
    blob = np.frombuffer(pickle.dumps(payload), np.uint8)

    def _run():
        # two rounds: sizes first (payloads differ per host), then the
        # max-size padded byte buffers
        sizes = np.asarray(multihost_utils.process_allgather(
            np.asarray([blob.size], np.int64))).ravel()
        padded = np.zeros(int(sizes.max()), np.uint8)
        padded[:blob.size] = blob
        out = np.asarray(multihost_utils.process_allgather(padded))
        return [pickle.loads(bytes(out[i][:int(sizes[i])]))
                for i in range(out.shape[0])]

    ex = concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="ds-host-exchange")
    try:
        return ex.submit(_run).result(timeout=timeout)
    except concurrent.futures.TimeoutError as e:
        raise TimeoutError(
            f"host-state exchange timed out after {timeout}s — a peer "
            "process is unreachable or wedged") from e
    finally:
        # wait=False: on timeout the gather thread is stuck in a collective;
        # joining it would reintroduce the very hang this guard removes
        ex.shutdown(wait=False)


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method=None,
                     dist_init_required=None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1,
                     mesh_axes: Optional[dict] = None):
    """Initialize multi-host JAX (if applicable) and the global mesh.

    Reference: ``comm/comm.py:619``. On TPU the rendezvous is
    `jax.distributed.initialize` (coordinator discovered from env:
    COORDINATOR_ADDRESS / MEGASCALE / SLURM / OpenMPI env, mirroring
    `mpi_discovery` comm.py:688), after which `jax.devices()` is global.
    """
    global _INITIALIZED

    # scheduler env discovery: ssh fan-out (JAX_*, always honored), plus
    # mpirun (OMPI_*) / srun (SLURM_*) / pdsh (DS_HOSTLIST) probing unless
    # auto_mpi_discovery=False — see mpi_discovery
    coord, nproc, pid = mpi_discovery(distributed_port, auto=auto_mpi_discovery)
    if rank >= 0:
        pid = rank
    if world_size > 0:
        nproc = world_size
    # NOTE: decide from env only — touching jax.process_count() here would
    # initialize the XLA backend and make jax.distributed.initialize raise
    # ("must be called before any JAX computations").
    if coord and nproc > 1 and not _INITIALIZED:
        if verbose:
            logger.info(f"init_distributed: coordinator={coord} procs={nproc} id={pid}")
        _initialize_distributed_guarded(coord, nproc, pid, timeout)
    if not mesh_is_initialized():
        set_mesh_context(MeshContext.create(axis_sizes=mesh_axes))
    _INITIALIZED = True
    return get_mesh_context()


def is_initialized():
    return _INITIALIZED or mesh_is_initialized()


def initialize_mesh_device(mesh_shape, mesh_axis_names):
    """Reference ``comm.py:603``; returns the global MeshContext."""
    sizes = dict(zip(mesh_axis_names, mesh_shape))
    ctx = MeshContext.create(axis_sizes=sizes, axis_order=tuple(mesh_axis_names))
    set_mesh_context(ctx)
    return ctx


# ---------------------------------------------------------------------------
# topology queries
# ---------------------------------------------------------------------------


def get_world_size(group: AxisNames = None) -> int:
    return get_mesh_context().axis_size(_norm_group(group))


def get_rank(group: AxisNames = None) -> int:
    """Host-level rank = process index (SPMD single-controller semantics).
    For a per-device rank along mesh axes inside a traced function, use
    `get_axis_index`."""
    return jax.process_index()


def get_local_rank() -> int:
    return 0


def get_axis_index(axis: AxisNames):
    """In-trace rank along `axis` (flattened over multiple axes)."""
    axes = _norm_group(axis)
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# in-trace collectives (hot path)
# ---------------------------------------------------------------------------

_REDUCE_FNS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
    ReduceOp.AVG: lax.pmean,
}

_EAGER_JIT_CACHE = {}


def _input_spec(a):
    if hasattr(a, "sharding") and isinstance(a.sharding, NamedSharding):
        return a.sharding.spec
    return P()


def _eager_collective(key, make_fn, tensor, group, out_spec=None):
    """Run an axis-collective eagerly over the global mesh via shard_map.

    `key` must uniquely identify the computation (op name + static params);
    jitted callables are cached on (key, axes, in_spec, out_spec) so repeated
    eager collectives don't retrace.
    """
    ctx = get_mesh_context()
    axes = _norm_group(group)
    in_spec = _input_spec(tensor)
    out_spec = in_spec if out_spec is None else out_spec
    cache_key = (key, axes, in_spec, out_spec, ctx.epoch)
    fn = _EAGER_JIT_CACHE.get(cache_key)
    if fn is None:
        fn = jax.jit(
            jax.shard_map(make_fn(axes), mesh=ctx.mesh, in_specs=(in_spec, ),
                          out_specs=out_spec, check_vma=False))
        _EAGER_JIT_CACHE[cache_key] = fn
    return fn(tensor)


def _reduce_in_trace(x, op, axes):
    if op == ReduceOp.PRODUCT:
        # No native product collective: gather and multiply (correct for
        # zeros/negatives, unlike exp(psum(log)) tricks).
        g = lax.all_gather(x, axes, axis=0, tiled=False)
        return jnp.prod(g, axis=0)
    if op not in _REDUCE_FNS:
        raise NotImplementedError(f"ReduceOp {op} is not supported on TPU")
    return _REDUCE_FNS[op](x, axes)


@timed_op
def all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, group: AxisNames = None, async_op=False, **kw):
    axes = _norm_group(group)
    if _in_trace(tensor):
        out = _reduce_in_trace(tensor, op, axes)
    else:
        out = _eager_collective(("all_reduce", op), lambda ax: (lambda x: _reduce_in_trace(x, op, ax)),
                                tensor, group)
    return CommHandle(out) if async_op else out


def inference_all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, group: AxisNames = None, **kw):
    """Reference comm.py:500 fast path — identical on TPU (XLA picks the
    algorithm); kept for API parity."""
    return all_reduce(tensor, op=op, group=group)


@timed_op
def all_gather(tensor, group: AxisNames = None, axis: int = 0, tiled: bool = True, async_op=False, **kw):
    """Gather shards along `axis`. In-trace this is `lax.all_gather(tiled=)`.
    Reference: all_gather_into_tensor (comm.py:317).

    Eager semantics: every participant's *local shard* is concatenated and the
    result is replicated — i.e. a sharded global array comes back with the
    same content, replicated; a replicated one comes back tiled `n` times
    (matching torch, where each rank contributes its local copy)."""
    axes = _norm_group(group)
    if _in_trace(tensor):
        out = lax.all_gather(tensor, axes, axis=axis, tiled=tiled)
    else:
        out = _eager_collective(("all_gather", axis, tiled),
                                lambda ax: (lambda x: lax.all_gather(x, ax, axis=axis, tiled=tiled)),
                                tensor, group, out_spec=P())
    return CommHandle(out) if async_op else out


# reference-parity aliases
def all_gather_into_tensor(output_tensor, tensor, group=None, async_op=False):
    res = all_gather(tensor, group=group, axis=0, tiled=True, async_op=async_op)
    return res


def has_all_gather_into_tensor():
    return True


def has_reduce_scatter_tensor():
    return True


@timed_op
def reduce_scatter(tensor, group: AxisNames = None, axis: int = 0, op: ReduceOp = ReduceOp.SUM,
                   async_op=False, **kw):
    """Reduce-scatter along `axis` (reference reduce_scatter_tensor comm.py:257)."""
    axes = _norm_group(group)

    n = get_mesh_context().axis_size(axes)

    def _make(ax):

        def _rs(x):
            out = lax.psum_scatter(x, ax, scatter_dimension=axis, tiled=True)
            if op == ReduceOp.AVG:
                out = out / n
            return out

        return _rs

    if _in_trace(tensor):
        out = _make(axes)(tensor)
    else:
        # Eager: output is sharded along `axis` over the group — rank k holds
        # the reduced k-th chunk; assembled global = elementwise reduction of
        # the participants' local tensors.
        spec = [None] * tensor.ndim
        spec[axis] = axes if len(axes) > 1 else axes[0]
        out = _eager_collective(("reduce_scatter", op, axis), _make, tensor, group,
                                out_spec=P(*spec))
    return CommHandle(out) if async_op else out


def reduce_scatter_tensor(output_tensor, tensor, op=ReduceOp.SUM, group=None, async_op=False):
    return reduce_scatter(tensor, group=group, op=op, async_op=async_op)


@timed_op
def all_to_all_single(tensor, group: AxisNames = None, split_axis: int = 0, concat_axis: int = 0,
                      async_op=False, **kw):
    """All-to-all (reference comm.py:360): split `split_axis` into world
    chunks, exchange, concatenate on `concat_axis`. The Ulysses hot op."""
    axes = _norm_group(group)

    def _make(ax):
        return lambda x: lax.all_to_all(x, ax, split_axis=split_axis, concat_axis=concat_axis, tiled=True)

    if _in_trace(tensor):
        out = _make(axes)(tensor)
    else:
        # Eager: treat the global array as sharded over `split_axis`; the
        # exchange moves the sharding to `concat_axis` with content preserved
        # (the Ulysses seq<->head reshard). Reshard input if needed.
        ctx = get_mesh_context()
        ax = axes if len(axes) > 1 else axes[0]
        in_spec = [None] * tensor.ndim
        in_spec[split_axis] = ax
        out_spec = [None] * tensor.ndim
        out_spec[concat_axis] = ax
        tensor = jax.device_put(tensor, ctx.sharding(*in_spec))
        out = _eager_collective(("all_to_all", split_axis, concat_axis), _make, tensor, group,
                                out_spec=P(*out_spec))
    return CommHandle(out) if async_op else out


def all_to_all(output_tensor_list, input_tensor_list, group=None, async_op=False):
    """List form: stack → all_to_all_single → unstack."""
    x = jnp.stack(input_tensor_list, axis=0)
    out = all_to_all_single(x, group=group, split_axis=0, concat_axis=0)
    n = get_world_size(group)
    chunks = jnp.split(out, n, axis=0)
    return [c.squeeze(0) if c.shape[0] == 1 else c for c in chunks]


@timed_op
def broadcast(tensor, src: int = 0, group: AxisNames = None, async_op=False, **kw):
    """Every participant ends with src's value. In-trace: gather + index
    (XLA lowers to a broadcast-from-root collective)."""
    axes = _norm_group(group)

    def _make(ax):

        def _bc(x):
            g = lax.all_gather(x, ax, axis=0, tiled=False)
            return g[src]

        return _bc

    if _in_trace(tensor):
        out = _make(axes)(tensor)
    else:
        # Eager: every participant ends with participant `src`'s local value;
        # the result is replicated (output shape == the local shard shape).
        out = _eager_collective(("broadcast", src), _make, tensor, group, out_spec=P())
    return CommHandle(out) if async_op else out


def ppermute(tensor, perm, group: AxisNames = None):
    """Point-to-point ring shift; the TPU analog of send/recv pairs
    (reference pipe/p2p.py). perm = list of (src, dst) pairs."""
    axes = _norm_group(group)
    return lax.ppermute(tensor, axes[0] if len(axes) == 1 else axes, perm=perm)


def send(tensor, dst, group=None, tag=0):
    raise NotImplementedError(
        "Raw send/recv is not expressible in SPMD/XLA; use comm.ppermute "
        "(both ends participate) — see parallel/pipe.py for the schedule-level replacement.")


def recv(tensor, src, group=None, tag=0):
    raise NotImplementedError(
        "Raw send/recv is not expressible in SPMD/XLA; use comm.ppermute.")


def barrier(group: AxisNames = None):
    """Host-level barrier: drain device queues; in multi-host, a tiny psum."""
    jax.effects_barrier()
    if jax.process_count() > 1:
        x = jnp.ones((), dtype=jnp.int32)
        jax.block_until_ready(
            _eager_collective(("barrier", ), lambda ax: (lambda v: lax.psum(v, ax)), x, group,
                              out_spec=P()))


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False):
    return barrier(group)


# coalesced forms: under XLA, passing a list and letting the compiler fuse is
# the coalescing (reference comm.py all_reduce_coalesced / all_gather_coalesced)
def all_reduce_coalesced(tensors, op=ReduceOp.SUM, group=None, async_op=False):
    return [all_reduce(t, op=op, group=group) for t in tensors]


def all_gather_coalesced(tensors, group=None, async_op=False):
    return [all_gather(t, group=group) for t in tensors]


def reduce_scatter_coalesced(tensors, group=None, async_op=False):
    """Reference ``runtime/comm/coalesced_collectives.py:81``."""
    return [reduce_scatter(t, group=group) for t in tensors]


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, async_op=False):
    """SPMD has no rooted reduce; all participants get the result (superset
    of reference semantics)."""
    return all_reduce(tensor, op=op, group=group, async_op=async_op)


def gather(tensor, gather_list=None, dst=0, group=None, async_op=False):
    return all_gather(tensor, group=group, async_op=async_op)


def scatter(tensor, scatter_list=None, src=0, group=None, async_op=False):
    raise NotImplementedError("scatter from a root is host-side under SPMD; use jax.device_put with a sharding")


# ---------------------------------------------------------------------------
# logging controls (reference comm.py:404-434)
# ---------------------------------------------------------------------------


def configure(deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None, verbose=None, debug=None):
    cl = get_comms_logger()
    if deepspeed_config is not None:
        cl.configure(deepspeed_config)
    if enabled is not None:
        cl.enabled = enabled
    if prof_all is not None:
        cl.prof_all = prof_all
    if prof_ops is not None:
        cl.prof_ops = prof_ops
    if verbose is not None:
        cl.verbose = verbose
    if debug is not None:
        cl.debug = debug


def log_summary(show_straggler=False):
    return get_comms_logger().log_all(show_straggler=show_straggler)
