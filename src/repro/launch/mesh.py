"""Device-mesh construction and multi-process bring-up for the grid
runtime — the hardware seam everything above ``repro.core`` stands on.

What lives here, bottom-up:

  * :func:`make_site_mesh` — the 1-D grid-site mesh (one device per paper
    "site") the single-host runtime's shard_map synchronization runs on;
    returns None when the host has too few devices and callers fall back
    to the bit-identical pooled path.
  * :func:`init_multihost` / :func:`make_multihost_mesh` — bring up
    ``jax.distributed`` (gloo CPU collectives selected BEFORE backend
    init; idempotent) and build the same site mesh over the GLOBAL
    device set, so the identical SiteJob DAGs distribute across hosts.
  * :func:`site_ownership` — the deterministic ``site -> process`` map
    (capacity-proportional greedy) that gives every grid site exactly
    one executing process under ``runtime.backends.MultiHostBackend``.
  * :func:`allgather_bytes` / :func:`allgather_payload` — the shipment
    wire: variable-length bytes (then packed pytrees) gathered across
    processes; the ONLY cross-process traffic the multihost backend
    performs, wave-fused so collectives scale with ready waves.
  * :func:`enable_compile_cache` — the persistent compile cache every
    entry point turns on first.

Everything is kept as FUNCTIONS (never module-level constants) so
importing this module never touches jax device state — a multi-device
CPU run must set XLA_FLAGS=--xla_force_host_platform_device_count
BEFORE first jax init, and ``init_multihost`` must run before the first
backend query.
"""

from __future__ import annotations

import jax


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory — call it first thing in an entry point, before
    anything compiles (``chip_smoke.py``, ``python -m repro.launch.serve``
    and the benchmark mains do; tests do not).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache lives in
    ``.jax_cache/`` at the root of the checkout: a fixed path, because
    the path is part of each entry's key and a moving directory never
    hits.  The minimum compile time worth caching is lowered so the
    one-to-two-second Pallas kernel compiles are kept too."""
    import os
    from pathlib import Path

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parents[3]
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return jax.config.jax_compilation_cache_dir


def init_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    cpu_collectives: str = "gloo",
) -> bool:
    """Initialize ``jax.distributed`` for a multi-process (multi-host)
    mesh; returns True when this jax runtime is multi-process afterwards.

    Idempotent: already-initialized runtimes (or single-process calls
    with no coordinator) return without touching jax state.  On CPU the
    cross-process collective implementation is selected BEFORE backend
    init (``gloo`` ships with jaxlib and makes psum/all_gather work
    across host processes — the two-subprocess smoke test exercises it);
    TPU/GPU runtimes ignore the flag.
    """
    if coordinator_address is None and num_processes is None:
        # nothing to initialize: report the launcher-provided topology
        # (safe to touch the backend here — no distributed init follows)
        return jax.process_count() > 1
    # ORDER MATTERS: jax.distributed.initialize must run before ANY jax
    # computation/backend query (jax.devices, jax.process_count, jit),
    # so the collective flag is set first and the backend only queried
    # after init.
    if cpu_collectives:
        jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        # idempotence: a runtime initialized by the launcher (or an
        # earlier backend) is fine; anything else is a real error
        if "already" not in str(e):
            raise
    return jax.process_count() > 1


def make_multihost_mesh(n_sites: int | None = None, axis: str = "sites"):
    """1-D grid-site mesh over the GLOBAL device set of a multi-process
    runtime (``init_multihost`` first) — the multi-host counterpart of
    ``make_site_mesh``: every process sees the same mesh spanning every
    host's devices, so the same SiteJob DAGs and shard_map collectives
    distribute across hosts for real.

    ``n_sites=None`` uses every global device; otherwise the first
    ``n_sites`` (None is returned when the global runtime is too small,
    matching ``make_site_mesh``'s fallback contract).
    """
    devs = jax.devices()
    n = len(devs) if n_sites is None else n_sites
    if n < 1 or len(devs) < n:
        return None
    return jax.make_mesh((n,), (axis,), devices=devs[:n])


def site_ownership(
    sites,
    n_processes: int | None = None,
    mesh=None,
    site_weights: dict[int, float] | None = None,
) -> dict[int, int]:
    """Explicit ``site -> process`` ownership map for true multi-host
    execution: every grid site's jobs execute on exactly one process and
    only their RESULTS ship over the collective.

    Assignment is least-relative-load greedy over sorted site ids
    (deterministic; ties break to the lowest process id):

      * ``mesh`` given — the candidate processes and their capacities are
        derived from the global device mesh (capacity = local device
        count), so a process holding more of the mesh owns
        proportionally more sites;
      * otherwise — ``n_processes`` unit-capacity processes.

    ``site_weights`` (site -> load units, e.g. per-site worker slots)
    skews the balance toward lighter owners for heavy sites; UNIFORM
    weights — such as the scalar ``GridModel.workers_per_site`` — cancel
    out and reduce to round-robin, so only genuinely per-site
    heterogeneity changes the map.

    Deterministic on every process by construction — all inputs are
    global state, so every process derives the identical map.
    """
    site_ids = sorted(set(int(s) for s in sites))
    if mesh is not None:
        capacity: dict[int, int] = {}
        for d in mesh.devices.flat:
            capacity[int(d.process_index)] = capacity.get(int(d.process_index), 0) + 1
    else:
        n_proc = int(n_processes if n_processes is not None else jax.process_count())
        if n_proc < 1:
            raise ValueError(f"n_processes must be >= 1, got {n_proc}")
        capacity = dict.fromkeys(range(n_proc), 1)
    load = dict.fromkeys(capacity, 0.0)
    owner: dict[int, int] = {}
    for s in site_ids:
        w = float(site_weights.get(s, 1.0)) if site_weights else 1.0
        pid = min(capacity, key=lambda p: (load[p] / capacity[p], p))
        owner[s] = pid
        load[pid] += max(w, 1e-9)
    return owner


def allgather_bytes(data: bytes) -> list[bytes]:
    """Gather one variable-length bytes payload per process (identity on a
    single-process runtime) — the wire that ships owned-site results.

    Two ``process_allgather`` rounds: payload lengths first, then the
    max-length-padded uint8 buffers; each process's slice is returned in
    process-id order.  This is the ONLY cross-process communication the
    multihost backend performs — one shipment per executed job, i.e. the
    paper's synchronization traffic and nothing else.
    """
    import numpy as np

    if jax.process_count() <= 1:
        return [data]
    from jax.experimental.multihost_utils import process_allgather

    lens = np.asarray(
        process_allgather(np.asarray([len(data)], dtype=np.int64))
    ).reshape(-1)
    cap = max(int(lens.max()), 1)
    buf = np.zeros((cap,), dtype=np.uint8)
    if data:
        buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    mat = np.asarray(process_allgather(buf)).reshape(len(lens), cap)
    return [mat[p, : int(lens[p])].tobytes() for p in range(len(lens))]


def allgather_payload(obj) -> list:
    """One-object-per-process shipment over :func:`allgather_bytes`:
    pack an arbitrary pytree payload (``compat.pack_payload`` — jax array
    leaves to host numpy, everything else pickled), gather every
    process's bytes in ONE ``allgather_bytes`` round, and unpack each
    slice.  This is the batched-shipment wire: the multihost backend
    ships a whole ready wave's result dict through one call instead of
    one ``allgather_bytes`` per job, so the collective count scales with
    waves, not jobs."""
    from repro.compat import pack_payload, unpack_payload

    return [unpack_payload(b) for b in allgather_bytes(pack_payload(obj))]


def make_site_mesh(n_sites: int, axis: str = "sites"):
    """1-D grid-site mesh for the mining runtime (one device per paper
    "site"), or None when the host exposes fewer devices than sites —
    callers fall back to the pooled vmap path.  Multi-device CPU tests get
    their devices from xla_force_host_platform_device_count."""
    devs = jax.devices()
    if n_sites < 1 or len(devs) < n_sites:
        return None
    return jax.make_mesh((n_sites,), (axis,), devices=devs[:n_sites])
