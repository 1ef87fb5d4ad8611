"""Execute a circuit-switching schedule JAX-natively with lax.ppermute.

Each perfect matching of a Vermilion period is exactly one ``ppermute``
permutation over a mesh axis: the optical circuits u->v become ICI sends
shard u -> shard v.  This module turns a :class:`~repro.core.schedule.Schedule`
into collective programs usable inside ``shard_map``:

* :func:`schedule_permute` — deliver per-destination chunks over one period.
* :func:`optical_allgather` — AllGather built from the schedule's circuits
  (this is how Appendix A's traffic estimation rides for free).
* :func:`optical_allreduce` — ring all-reduce whose ring is one of the
  schedule's cyclic matchings.

:func:`run_schedule_demo` runs all three over every device JAX sees: the
chips of a TPU host over ICI, or fake CPU devices made with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the tests do this in
a subprocess).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .schedule import Schedule

__all__ = [
    "schedule_permute",
    "optical_allgather",
    "optical_allreduce",
    "run_schedule_demo",
]


def _perm_pairs(perm: np.ndarray) -> list[tuple[int, int]]:
    return [(int(u), int(v)) for u, v in enumerate(perm) if int(u) != int(v)]


def _first_fire(sched: Schedule) -> np.ndarray:
    """(T, n) bool: matching t carries pair (u, perms[t,u]) for the first
    time in the period (duplicate circuits are send-once no-ops)."""
    seen: set[tuple[int, int]] = set()
    out = np.zeros((sched.T, sched.n), dtype=bool)
    for t in range(sched.T):
        for u, v in enumerate(sched.perms[t]):
            p = (int(u), int(v))
            if p[0] != p[1] and p not in seen:
                seen.add(p)
                out[t, u] = True
    return out


def schedule_permute(x: jax.Array, sched: Schedule, axis_name: str) -> jax.Array:
    """Deliver per-destination chunks along the schedule's circuits.

    ``x``: (n, ...) on each shard; row v is the payload destined for shard v.
    Returns (n, ...); row u is the payload received from shard u (row self =
    own payload). Requires every ordered pair to appear in the period —
    guaranteed by Vermilion's oblivious residual phase.
    """
    n = sched.n
    idx = jax.lax.axis_index(axis_name)
    fire = jnp.asarray(_first_fire(sched), dtype=jnp.bool_)
    out = jnp.zeros_like(x)
    out = out.at[idx].set(x[idx])
    for t in range(sched.T):
        pairs = _perm_pairs(sched.perms[t])
        if not pairs:
            continue
        perm_arr = jnp.asarray(sched.perms[t], dtype=jnp.int32)
        dest = perm_arr[idx]
        live = fire[t, idx]
        payload = jnp.where(live, x[dest], jnp.zeros_like(x[dest]))
        moved = jax.lax.ppermute(payload, axis_name, pairs)
        src = jnp.argsort(perm_arr)[idx]
        out = out.at[src].add(jnp.where(src != idx, moved, jnp.zeros_like(moved)))
    return out


def optical_allgather(x: jax.Array, sched: Schedule, axis_name: str) -> jax.Array:
    """AllGather of per-shard rows using only the schedule's circuits.
    Returns (n, *x.shape), identical on every shard after one period."""
    n = sched.n
    idx = jax.lax.axis_index(axis_name)
    have = jnp.zeros((n,) + x.shape, x.dtype).at[idx].set(x)
    mask = jnp.zeros((n,), dtype=bool).at[idx].set(True)
    for t in range(sched.T):
        pairs = _perm_pairs(sched.perms[t])
        if not pairs:
            continue
        moved = jax.lax.ppermute(have, axis_name, pairs)
        mmask = jax.lax.ppermute(mask, axis_name, pairs)
        take = mmask & ~mask
        have = jnp.where(take.reshape((n,) + (1,) * x.ndim), moved, have)
        mask = mask | mmask
    return have


def _ring_from_schedule(sched: Schedule) -> list[tuple[int, int]] | None:
    """If some matching is a single n-cycle, use it as the ring."""
    for t in range(sched.T):
        p = sched.perms[t]
        seen, u = set(), 0
        for _ in range(sched.n):
            if u in seen:
                break
            seen.add(u)
            u = int(p[u])
        if len(seen) == sched.n and u == 0:
            return _perm_pairs(p)
    return None


def optical_allreduce(x: jax.Array, sched: Schedule, axis_name: str) -> jax.Array:
    """Ring all-reduce whose ring is a cyclic matching of the schedule
    (falls back to the canonical +1 ring)."""
    n = sched.n
    ring = _ring_from_schedule(sched) or [(i, (i + 1) % n) for i in range(n)]
    acc = x
    buf = x
    for _ in range(n - 1):
        buf = jax.lax.ppermute(buf, axis_name, ring)
        acc = acc + buf
    return acc


def _ramp(shape: tuple) -> jax.Array:
    """Float32 payload whose rows along the last axis all differ, made of
    integers small enough that sums are exact in any order."""
    i = jnp.arange(int(np.prod(shape)), dtype=jnp.int32)
    return (i % 251 + 256 * (i // shape[-1])).astype(
        jnp.float32).reshape(shape)


def _lands_everywhere(out: jax.Array, ref: jax.Array, devs) -> bool:
    """``out`` has a shard on every device of ``devs`` and each shard equals
    the reference's shard on that device, bit for bit."""
    got = {s.device: s for s in out.addressable_shards}
    want = {s.device: s for s in ref.addressable_shards}
    if set(got) != set(devs) or set(want) != set(devs):
        return False
    return all(got[d].index == want[d].index
               and np.array_equal(np.asarray(got[d].data),
                                  np.asarray(want[d].data))
               for d in devs)


def run_schedule_demo(n: int | None = None, seed: int = 0,
                      row_elems: int = 4) -> dict:
    """Vermilion-scheduled all-gather, all-reduce and chunk delivery over
    the first ``n`` devices (default: all of them), each compared with
    XLA's own collective on the same mesh: ``all_gather``, ``psum`` and a
    transpose.  Every device holds ``row_elems`` float32 values of each
    payload.  An entry is True when every device holds the reference's
    result, bit for bit (the payloads are small integers, so the ring sum
    is exact in any order)."""
    from .traffic import uniform
    from .schedule import vermilion_schedule

    devs = jax.devices()
    n = len(devs) if n is None else n
    if not 2 <= n <= len(devs):
        raise RuntimeError(f"run_schedule_demo needs 2 or more devices and "
                           f"JAX sees {len(devs)} (asked for n={n})")
    devs = devs[:n]
    mesh = Mesh(np.array(devs), ("pod",))
    rows = NamedSharding(mesh, P("pod"))
    sched = vermilion_schedule(uniform(n), k=2, d_hat=1, seed=seed)

    def smap(body, out_specs):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                     out_specs=out_specs, check_vma=False))

    ramp = jax.jit(_ramp, static_argnums=0, out_shardings=rows)
    x = ramp((n, row_elems))                    # x[s] is shard s's row
    ag = smap(lambda xs: optical_allgather(xs[0], sched, "pod"), P())(x)
    ag_ref = smap(lambda xs: jax.lax.all_gather(xs[0], "pod"), P())(x)
    ar = smap(lambda xs: optical_allreduce(xs[0], sched, "pod")[None],
              P("pod"))(x)
    ar_ref = smap(lambda xs: jax.lax.psum(xs, "pod"), P("pod"))(x)

    # chunk delivery: payload[s, v] is what shard s addresses to v; after
    # one period shard s's row u == payload[u, s], the transpose
    chunk = max(row_elems // n, 1)
    payload = ramp((n, n, chunk))
    sp = smap(lambda p: schedule_permute(p[0], sched, "pod")[None],
              P("pod"))(payload)
    sp_ref = jax.jit(lambda p: jnp.swapaxes(p, 0, 1),
                     out_shardings=rows)(payload)
    return {"allgather_ok": _lands_everywhere(ag, ag_ref, devs),
            "allreduce_ok": _lands_everywhere(ar, ar_ref, devs),
            "permute_ok": _lands_everywhere(sp, sp_ref, devs)}
