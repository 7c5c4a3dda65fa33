"""Adversarial fault injection for the gossip overlay (§V.4 / Table III).

The paper's security argument is qualitative: Byzantine publishers are
starved of approvals by the accuracy-weighted tip selection, and corrupted
model payloads are caught because every transaction's content is
hash-addressed. This module makes those claims *executable*: per-node
adversary ROLES are injected inside the SAME jitted round bodies both
engines run (`repro.net.gossip`'s tick scan/while paths and
`repro.net.events`' delivery batches) — device-resident, so a faulted run
is still one `lax.scan`/`lax.while_loop` dispatch per advance window — and
the defenses the paper assumes (digest verification on receive, re-fetch
from alternate holders, quarantine of misbehaving links) are implemented
against them.

Roles (one per node, static for the run):

``ROLE_HONEST``     the PR-3 node, unchanged.
``ROLE_CRASH``      dark for ``t in [crash_start, crash_end)``: every edge
                    touching the node is cut (fail-stop churn window; the
                    node neither serves nor hears gossip, then recovers).
``ROLE_ECLIPSE``    adjacency rewrite around ``eclipse_target``: the
                    target's links to non-attackers are cut both ways, so
                    its view of the DAG is whatever the attackers relay.
``ROLE_SELECTIVE``  forwards each outgoing edge with probability
                    ``forward_prob`` only (selective forwarding / gray
                    hole) — an availability attack the redundant overlay
                    paths must absorb.
``ROLE_SPOOF``      serves chunk payloads whose bytes do not match the
                    announced content digest (rate ``spoof_rate`` per
                    admitted chunk). Requires bank gossip — metadata rows
                    are self-authenticating, payloads are where spoofing
                    bites.
``ROLE_SYBIL``      forges the full approver bitset on every row of its
                    own replica before gossiping it — the inflation attack
                    the exact approver-set union (PR 7) bounds at N and
                    crossing-gated contribution counters keep out of the
                    §V.2 rates.

Defense side (``verify_digests=True``, the default):

* every admitted chunk is digest-checked on receive
  (``repro.kernels.chunk_transfer.transfer_verify``) and a mismatch is
  dropped BEFORE it can set a presence bit — corrupted payloads never
  reach ``commit_chunks``/``gate_view``;
* a rejecting link zeroes its rolled-over credit (back-off) and charges
  the sender one rejection per bad chunk; at ``quarantine_after``
  cumulative rejections the link is cut for good and the striping in
  ``transfer_select`` re-routes the chunks to alternate holders — bounded
  re-fetch, paid for by the attacker's wasted bytes (spent is charged for
  rejected transfers too);
* cumulative per-sender rejections feed
  ``repro.core.anomaly.rejection_credit`` so the FL driver can bias tip
  selection away from quarantined publishers.

PRNG discipline: fault randomness derives from the round's existing
sub-key via ``jax.random.fold_in`` with fixed salts — the main key stream
sees the exact same split sequence as the un-faulted program, so a config
whose roles are all-HONEST is bitwise the ``faults=None`` path (tested),
and enabling e.g. a crash window does not perturb the drop-loss draws.

``faults=None`` in `GossipNetwork` keeps every existing code path
literally untouched (the ``obs=None`` pattern). The tick engine's jit
factories in gossip.py dispatch to the faulted twins below only when a
``FaultConfig`` is passed; the event engine's one loop body
(``events._build_advance``) calls ``fault_edges`` / ``sybil_inflate``
from its delivery round and ``_fault_chunk_service`` from its bank
service, and traces neither without one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dag import DagState
from repro.kernels import chunk_transfer as chunk_kernel
from repro.net import bank as bank_lib
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib

ROLE_HONEST = 0
ROLE_CRASH = 1
ROLE_ECLIPSE = 2
ROLE_SELECTIVE = 3
ROLE_SPOOF = 4
ROLE_SYBIL = 5

ROLE_NAMES = ("honest", "crash", "eclipse", "selective", "spoof", "sybil")

# fold_in salts: fault draws branch off the round's sub-key without
# advancing the main stream (see module docstring)
_SALT_EDGES = 7
_SALT_SPOOF = 11


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Static, hashable adversary assignment (a jit-factory cache key).

    ``roles``            one ``ROLE_*`` per node (tuple, length N).
    ``crash_start/end``  wall-clock window CRASH nodes are dark. The tick
                         engine evaluates it at the tick's sample instant
                         ``(tick + 1) * sync_period`` (the telemetry
                         convention); on the ideal wire (period <= 0) every
                         tick sits at t = 0, so the window either always or
                         never applies there.
    ``eclipse_target``   the node ECLIPSE attackers isolate (required when
                         any ECLIPSE role is assigned).
    ``forward_prob``     SELECTIVE nodes forward each edge with this
                         probability per round.
    ``spoof_rate``       probability a SPOOF node corrupts an admitted
                         chunk (1.0 = every chunk it serves is garbage).
    ``verify_digests``   the defense switch: True drops corrupted chunks on
                         receive and quarantines repeat offenders; False
                         lets them through (attack-success measurement —
                         ``FaultState.tainted`` then tracks the infection).
    ``quarantine_after`` cumulative rejections at which a (receiver,
                         sender) link is cut permanently.
    """

    roles: Tuple[int, ...]
    crash_start: float = 0.0
    crash_end: float = float("inf")
    eclipse_target: int = -1
    forward_prob: float = 0.5
    spoof_rate: float = 1.0
    verify_digests: bool = True
    quarantine_after: int = 3


class FaultState(NamedTuple):
    """Defense-side carry, threaded through the jitted loops (bank runs
    only — bankless fault paths are stateless edge/row rewrites)."""

    rejects: jnp.ndarray   # (N, N) int32  digest rejections: receiver i charged sender j
    tainted: jnp.ndarray   # (N, S, C) bool corrupted chunks accepted (verify off)


def init_fault_state(n: int, slots: int, chunks: int) -> FaultState:
    return FaultState(
        rejects=jnp.zeros((n, n), jnp.int32),
        tainted=jnp.zeros((n, slots, chunks), jnp.bool_),
    )


def validate_faults(cfg: FaultConfig, n: int, bank: bool) -> None:
    if len(cfg.roles) != n:
        raise ValueError(
            f"FaultConfig.roles has {len(cfg.roles)} entries for {n} nodes"
        )
    bad = [r for r in cfg.roles if r not in range(len(ROLE_NAMES))]
    if bad:
        raise ValueError(f"unknown fault roles: {bad!r}")
    if ROLE_ECLIPSE in cfg.roles and not 0 <= cfg.eclipse_target < n:
        raise ValueError(
            "ROLE_ECLIPSE assigned but eclipse_target is not a valid node"
        )
    if ROLE_SPOOF in cfg.roles and not bank:
        raise ValueError(
            "ROLE_SPOOF corrupts chunk payloads in flight — it requires "
            "bank gossip (construct GossipNetwork with bank_cfg)"
        )
    if cfg.quarantine_after < 1:
        raise ValueError("quarantine_after must be >= 1")


class _RoleMasks(NamedTuple):
    """Static per-role masks baked into the jitted bodies (numpy, traced as
    constants — roles never change mid-run)."""

    crash: np.ndarray         # (N,) bool
    eclipse_keep: np.ndarray  # (N, N) bool — edges the eclipse leaves alive
    selective: np.ndarray     # (N,) bool
    spoof: np.ndarray         # (N,) bool
    sybil: np.ndarray         # (N,) bool
    any_crash: bool
    any_selective: bool
    any_spoof: bool
    any_sybil: bool


@functools.lru_cache(maxsize=None)
def _role_masks(cfg: FaultConfig) -> _RoleMasks:
    roles = np.asarray(cfg.roles, np.int32)
    n = roles.shape[0]
    crash = roles == ROLE_CRASH
    selective = roles == ROLE_SELECTIVE
    spoof = roles == ROLE_SPOOF
    sybil = roles == ROLE_SYBIL
    attackers = roles == ROLE_ECLIPSE
    keep = np.ones((n, n), bool)
    if attackers.any():
        # the target keeps only its links to/from the attackers (and its
        # self-loop): everything it learns is relayed through them
        tgt = int(cfg.eclipse_target)
        allowed = attackers | (np.arange(n) == tgt)
        keep[tgt, :] = allowed
        keep[:, tgt] = allowed
    return _RoleMasks(
        crash=crash, eclipse_keep=keep, selective=selective, spoof=spoof,
        sybil=sybil, any_crash=bool(crash.any()),
        any_selective=bool(selective.any()), any_spoof=bool(spoof.any()),
        any_sybil=bool(sybil.any()),
    )


def fault_edges(cfg: FaultConfig, masks: _RoleMasks, t, fkey, edges):
    """Apply the edge-level attacks to a sampled/live edge mask.

    ``edges[i, j]`` = receiver i hears sender j (the engines' convention).
    Pure suppression — faults only remove deliveries, never add them — so
    an all-HONEST config returns ``edges`` bitwise. ``fkey`` is a
    ``fold_in`` branch of the round's sub-key; only SELECTIVE draws from
    it.
    """
    keep = jnp.asarray(masks.eclipse_keep)
    if masks.any_crash:
        dark = jnp.where(
            (t >= cfg.crash_start) & (t < cfg.crash_end),
            jnp.asarray(masks.crash), False,
        )
        keep = keep & ~dark[:, None] & ~dark[None, :]
    if masks.any_selective:
        u = jax.random.uniform(fkey, edges.shape)
        fwd = ~jnp.asarray(masks.selective)[None, :] | (u < cfg.forward_prob)
        keep = keep & fwd
    return edges & keep


def sybil_inflate(dags: DagState, masks: _RoleMasks) -> DagState:
    """SYBIL nodes forge the full approver bitset on their own rows.

    Runs on the stacked replica set after each round: every row a sybil
    node published *in its own replica* claims every node as an approver
    before the next gossip exchange relays it. The exact approver-set
    union (``core.dag.merge``) caps the damage at N distinct approvers and
    honest replicas' crossing-gated contribution counters never credit the
    forgeries — the attack inflates ``approval_count`` (rows stop looking
    like tips) but not the §V.2 contribution rates.
    """
    if not masks.any_sybil:
        return dags
    r = dags.publisher.shape[0]
    own = dags.publisher == jnp.arange(r, dtype=dags.publisher.dtype)[:, None]
    forge = own & jnp.asarray(masks.sybil)[:, None]
    approvers = dags.approvers | forge[:, :, None]
    return dags._replace(
        approvers=approvers,
        approval_count=jnp.sum(approvers.astype(jnp.int32), axis=-1),
    )


def quarantined(fstate: FaultState, cfg: FaultConfig) -> jnp.ndarray:
    """(N, N) bool — links cut by the rejection counter."""
    return fstate.rejects >= cfg.quarantine_after


def _fault_chunk_service(dags, bstate, fstate, digest, edges, cap_bytes,
                         chunk_bytes, skey, cfg, masks, bank_impl):
    """The fault-aware bank service step (mirrors ``bank.chunk_step``).

    Spoofed payloads are drawn per admitted chunk from ``skey`` (a
    ``fold_in`` branch — the main stream is untouched). With
    ``verify_digests`` the receive path becomes: recompute digests →
    reject mismatches (``transfer_verify``) → commit only verified chunks;
    a rejecting link loses its rolled-over credit (back-off) and repeat
    offenders are quarantined, at which point ``transfer_select``'s
    striping re-routes their chunks to alternate holders on the next
    service — bounded re-fetch with the attacker still billed the spent
    bytes. With verification off the corrupted chunks land and
    ``tainted`` tracks the infection (re-serving a tainted store corrupts
    downstream receivers too).

    Returns ``(bstate, fstate, pending)``.
    """
    r = edges.shape[0]
    s, c = bstate.have.shape[1], bstate.have.shape[2]
    m = s * c
    if cfg.verify_digests:
        edges = edges & ~quarantined(fstate, cfg)
    sat = chunk_kernel.chunk_dedup(bstate.have, digest, impl=bank_impl)
    ref = bank_lib.referenced_slots(dags, s)
    need = (ref[:, :, None] & ~sat).reshape(r, m)
    budget = bstate.credit + jnp.where(edges, cap_bytes, 0.0)
    afford = jnp.clip(
        jnp.floor(budget / chunk_bytes), 0, jnp.iinfo(jnp.int32).max
    ).astype(jnp.int32)
    take, take_link, spent_chunks, pending = chunk_kernel.transfer_select(
        need, sat.reshape(r, m), edges, afford, return_links=True
    )
    # which admitted transfers carry bytes that will not hash to the
    # announced digest: freshly spoofed by the sender, or re-served from a
    # store that accepted garbage earlier (verify-off infection)
    bad = fstate.tainted.reshape(r, m)[None, :, :]
    if masks.any_spoof:
        u = jax.random.uniform(skey, take_link.shape)
        bad = bad | (jnp.asarray(masks.spoof)[None, :, None]
                     & (u < cfg.spoof_rate))
    bad = take_link & bad
    spent = spent_chunks.astype(jnp.float32) * chunk_bytes
    if cfg.verify_digests:
        ok_take, rej = chunk_kernel.transfer_verify(take_link, bad)
        have = bstate.have | ok_take.reshape(r, s, c)
        # rejected bytes still crossed the wire (the attacker's bill);
        # the link's rolled-over budget is dropped as back-off
        credit = jnp.where(
            pending, budget - spent, jnp.where(edges, 0.0, bstate.credit)
        )
        credit = jnp.where(rej > 0, 0.0, credit)
        fstate = fstate._replace(rejects=fstate.rejects + rej)
    else:
        have = bstate.have | take.reshape(r, s, c)
        credit = jnp.where(
            pending, budget - spent, jnp.where(edges, 0.0, bstate.credit)
        )
        fstate = fstate._replace(
            tainted=fstate.tainted | jnp.any(bad, axis=1).reshape(r, s, c)
        )
    bstate = bank_lib.BankState(
        have=have, credit=credit, sent=bstate.sent + spent
    )
    return bstate, fstate, pending


# ---------------------------------------------------------------------------
# Tick engine: faulted variants of gossip.py's four jit factories
# ---------------------------------------------------------------------------


def _faulted_tick(impl, cfg, masks):
    """(dags, sub, tick, pm, adj, drop, stride, nbrs, period) ->
    (dags, edges, t): one faulted bankless tick body."""

    def tick_body(dags, sub, tick, pm, adj, drop, stride, nbr_idx, nbr_valid,
                  period):
        edges = gossip_lib._sample_edges(sub, tick, pm, adj, drop, stride)
        t = (tick.astype(jnp.float32) + 1.0) * period
        edges = fault_edges(
            cfg, masks, t, jax.random.fold_in(sub, _SALT_EDGES), edges
        )
        dags = gossip_lib._apply_round(dags, edges, nbr_idx, nbr_valid, impl)
        dags = sybil_inflate(dags, masks)
        return dags, edges, t

    return tick_body


@functools.lru_cache(maxsize=None)
def _advance_faults_jit(impl: str, faults: FaultConfig, obs=None):
    """Faulted ``_advance_jit``: same ONE-scan window, same PRNG splits —
    the fault layer only rewrites the sampled edge mask (and, for SYBIL,
    the post-round approver bitsets) inside the scan body."""
    masks = _role_masks(faults)
    tick_body = _faulted_tick(impl, faults, masks)

    if obs is None:
        def advance(dags, key, ticks, part_active, adj, drop, stride,
                    part_mask, nbr_idx, nbr_valid, period):
            def body(carry, xs):
                dags, key = carry
                tick, pact = xs
                key, sub = jax.random.split(key)
                pm = jnp.where(pact, part_mask, True)
                dags, _edges, _t = tick_body(
                    dags, sub, tick, pm, adj, drop, stride, nbr_idx,
                    nbr_valid, period,
                )
                return (dags, key), None

            (dags, key), _ = jax.lax.scan(
                body, (dags, key), (ticks, part_active)
            )
            return dags, key

        return jax.jit(advance)

    from repro import obs as obs_lib   # deferred: repro.obs imports repro.net

    def advance(dags, key, ticks, part_active, adj, drop, stride, part_mask,
                nbr_idx, nbr_valid, period, metrics, ring):
        def body(carry, xs):
            dags, key, metrics, ring = carry
            tick, pact = xs
            key, sub = jax.random.split(key)
            pm = jnp.where(pact, part_mask, True)
            new, edges, t = tick_body(
                dags, sub, tick, pm, adj, drop, stride, nbr_idx, nbr_valid,
                period,
            )
            metrics, ring = obs_lib.observe_round(
                obs, metrics, ring, t, dags, new, live_edges=edges
            )
            return (new, key, metrics, ring), None

        (dags, key, metrics, ring), _ = jax.lax.scan(
            body, (dags, key, metrics, ring), (ticks, part_active)
        )
        return dags, key, metrics, ring

    return jax.jit(advance)


@functools.lru_cache(maxsize=None)
def _converge_faults_jit(impl: str, faults: FaultConfig, obs=None):
    """Faulted ``_converge_jit``: the fixpoint flush under active faults.
    An eclipsed/crashed component that can make no further progress trips
    the stall exit exactly as a partition does."""
    masks = _role_masks(faults)
    tick_body = _faulted_tick(impl, faults, masks)

    if obs is None:
        def converge(dags, key, tick, part_mask, adj, drop, stride, limit,
                     stall_limit, nbr_idx, nbr_valid, period):
            def cond(carry):
                dags, _key, _tick, stalled, done = carry
                return (
                    ~replica_lib.replicas_synced(dags)
                    & (done < limit)
                    & (stalled < stall_limit)
                )

            def body(carry):
                dags, key, tick, stalled, done = carry
                key, sub = jax.random.split(key)
                new, _edges, _t = tick_body(
                    dags, sub, tick, part_mask, adj, drop, stride, nbr_idx,
                    nbr_valid, period,
                )
                stalled = jnp.where(
                    gossip_lib.trees_equal(new, dags), stalled + 1, 0
                )
                return (new, key, tick + 1, stalled, done + 1)

            dags, key, tick, _, done = jax.lax.while_loop(
                cond, body, (dags, key, tick, jnp.int32(0), jnp.int32(0)),
            )
            return dags, key, tick, done, replica_lib.replicas_synced(dags)

        return jax.jit(converge)

    from repro import obs as obs_lib

    def converge(dags, key, tick, part_mask, adj, drop, stride, limit,
                 stall_limit, nbr_idx, nbr_valid, period, metrics, ring):
        def cond(carry):
            dags, _key, _tick, stalled, done = carry[:5]
            return (
                ~replica_lib.replicas_synced(dags)
                & (done < limit)
                & (stalled < stall_limit)
            )

        def body(carry):
            dags, key, tick, stalled, done, metrics, ring = carry
            key, sub = jax.random.split(key)
            new, edges, t = tick_body(
                dags, sub, tick, part_mask, adj, drop, stride, nbr_idx,
                nbr_valid, period,
            )
            stalled = jnp.where(
                gossip_lib.trees_equal(new, dags), stalled + 1, 0
            )
            metrics, ring = obs_lib.observe_round(
                obs, metrics, ring, t, dags, new, live_edges=edges
            )
            return (new, key, tick + 1, stalled, done + 1, metrics, ring)

        dags, key, tick, _, done, metrics, ring = jax.lax.while_loop(
            cond, body,
            (dags, key, tick, jnp.int32(0), jnp.int32(0), metrics, ring),
        )
        return (dags, key, tick, done, replica_lib.replicas_synced(dags),
                metrics, ring)

    return jax.jit(converge)


@functools.lru_cache(maxsize=None)
def _advance_bank_faults_jit(impl: str, bank_impl, faults: FaultConfig,
                             obs=None, codec=None):
    """Faulted ``_advance_bank_jit``: rows merge over the faulted edge
    mask, then the fault-aware chunk service (spoofing, verification,
    back-off, quarantine) replaces ``chunk_step`` with the ``FaultState``
    threaded through the scan carry. ``codec`` (pre-mapped through
    ``delta_codec.codec_key``) prices chunks at encoded bytes — the
    attacker's rejected transfers are billed the COMPRESSED size too;
    ``codec=None`` keeps the literal raw-chunk program."""
    masks = _role_masks(faults)
    tick_body = _faulted_tick(impl, faults, masks)

    def serviced(dags, bstate, fstate, digest, edges, sub, cap_bytes,
                 chunk_bytes):
        if codec is not None:
            chunk_bytes = chunk_bytes * codec.wire_ratio()
        return _fault_chunk_service(
            dags, bstate, fstate, digest, edges, cap_bytes, chunk_bytes,
            jax.random.fold_in(sub, _SALT_SPOOF), faults, masks, bank_impl,
        )

    if obs is None:
        def advance(dags, bstate, fstate, digest, key, ticks, part_active,
                    adj, drop, stride, part_mask, nbr_idx, nbr_valid,
                    cap_bytes, chunk_bytes, period):
            def body(carry, xs):
                dags, bstate, fstate, key = carry
                tick_i, pact = xs
                key, sub = jax.random.split(key)
                pm = jnp.where(pact, part_mask, True)
                dags, edges, _t = tick_body(
                    dags, sub, tick_i, pm, adj, drop, stride, nbr_idx,
                    nbr_valid, period,
                )
                bstate, fstate, _pend = serviced(
                    dags, bstate, fstate, digest, edges, sub, cap_bytes,
                    chunk_bytes,
                )
                return (dags, bstate, fstate, key), None

            (dags, bstate, fstate, key), _ = jax.lax.scan(
                body, (dags, bstate, fstate, key), (ticks, part_active)
            )
            return dags, bstate, fstate, key

        return jax.jit(advance)

    from repro import obs as obs_lib

    def advance(dags, bstate, fstate, digest, key, ticks, part_active, adj,
                drop, stride, part_mask, nbr_idx, nbr_valid, cap_bytes,
                chunk_bytes, period, metrics, ring):
        def body(carry, xs):
            dags, bstate, fstate, key, metrics, ring = carry
            tick_i, pact = xs
            key, sub = jax.random.split(key)
            pm = jnp.where(pact, part_mask, True)
            new, edges, t = tick_body(
                dags, sub, tick_i, pm, adj, drop, stride, nbr_idx,
                nbr_valid, period,
            )
            newb, newf, _pend = serviced(
                new, bstate, fstate, digest, edges, sub, cap_bytes,
                chunk_bytes,
            )
            metrics, ring = obs_lib.observe_round(
                obs, metrics, ring, t, dags, new, live_edges=edges,
                bytes_delta=newb.sent - bstate.sent, bstate=newb,
                digest=digest, bank_impl=bank_impl, old_have=bstate.have,
                rejects=newf.rejects,
                rejects_delta=newf.rejects - fstate.rejects,
                quarantine_after=faults.quarantine_after,
            )
            return (new, newb, newf, key, metrics, ring), None

        (dags, bstate, fstate, key, metrics, ring), _ = jax.lax.scan(
            body, (dags, bstate, fstate, key, metrics, ring),
            (ticks, part_active)
        )
        return dags, bstate, fstate, key, metrics, ring

    return jax.jit(advance)


@functools.lru_cache(maxsize=None)
def _converge_bank_faults_jit(impl: str, bank_impl, faults: FaultConfig,
                              obs=None, codec=None):
    """Faulted ``_converge_bank_jit``. The stall check watches the
    ``FaultState`` too: rejections accruing toward quarantine are progress
    (the back-off/re-route cycle is still converging); once a spoofed
    stripe has re-routed and nothing moves for a full stride cycle the
    flush exits — ``synced`` is then honest about whether every referenced
    chunk VERIFIED, not merely arrived. ``codec`` prices chunks at encoded
    bytes (``codec=None`` keeps the literal raw-chunk program)."""
    masks = _role_masks(faults)
    tick_body = _faulted_tick(impl, faults, masks)

    def serviced(dags, bstate, fstate, digest, edges, sub, cap_bytes,
                 chunk_bytes):
        if codec is not None:
            chunk_bytes = chunk_bytes * codec.wire_ratio()
        return _fault_chunk_service(
            dags, bstate, fstate, digest, edges, cap_bytes, chunk_bytes,
            jax.random.fold_in(sub, _SALT_SPOOF), faults, masks, bank_impl,
        )

    def synced(dags, bstate, digest):
        return replica_lib.replicas_synced(dags) & (
            jnp.max(bank_lib.missing_chunks(dags, bstate, digest,
                                            impl=bank_impl)) == 0
        )

    if obs is None:
        def converge(dags, bstate, fstate, digest, key, tick0, part_mask,
                     adj, drop, stride, limit, stall_limit, nbr_idx,
                     nbr_valid, cap_bytes, chunk_bytes, period):
            def cond(carry):
                dags, bstate, _f, _key, _tick, stalled, done = carry
                return (
                    ~synced(dags, bstate, digest)
                    & (done < limit)
                    & (stalled < stall_limit)
                )

            def body(carry):
                dags, bstate, fstate, key, tick_i, stalled, done = carry
                key, sub = jax.random.split(key)
                new, edges, _t = tick_body(
                    dags, sub, tick_i, part_mask, adj, drop, stride,
                    nbr_idx, nbr_valid, period,
                )
                newb, newf, _pend = serviced(
                    new, bstate, fstate, digest, edges, sub, cap_bytes,
                    chunk_bytes,
                )
                still = gossip_lib.trees_equal(
                    (new, newb, newf), (dags, bstate, fstate)
                )
                stalled = jnp.where(still, stalled + 1, 0)
                return (new, newb, newf, key, tick_i + 1, stalled, done + 1)

            dags, bstate, fstate, key, tick_i, _, done = jax.lax.while_loop(
                cond, body,
                (dags, bstate, fstate, key, tick0, jnp.int32(0),
                 jnp.int32(0)),
            )
            return (dags, bstate, fstate, key, tick_i, done,
                    synced(dags, bstate, digest))

        return jax.jit(converge)

    from repro import obs as obs_lib

    def converge(dags, bstate, fstate, digest, key, tick0, part_mask, adj,
                 drop, stride, limit, stall_limit, nbr_idx, nbr_valid,
                 cap_bytes, chunk_bytes, period, metrics, ring):
        def cond(carry):
            dags, bstate, _f, _key, _tick, stalled, done = carry[:7]
            return (
                ~synced(dags, bstate, digest)
                & (done < limit)
                & (stalled < stall_limit)
            )

        def body(carry):
            (dags, bstate, fstate, key, tick_i, stalled, done,
             metrics, ring) = carry
            key, sub = jax.random.split(key)
            new, edges, t = tick_body(
                dags, sub, tick_i, part_mask, adj, drop, stride, nbr_idx,
                nbr_valid, period,
            )
            newb, newf, _pend = serviced(
                new, bstate, fstate, digest, edges, sub, cap_bytes,
                chunk_bytes,
            )
            still = gossip_lib.trees_equal(
                (new, newb, newf), (dags, bstate, fstate)
            )
            stalled = jnp.where(still, stalled + 1, 0)
            metrics, ring = obs_lib.observe_round(
                obs, metrics, ring, t, dags, new, live_edges=edges,
                bytes_delta=newb.sent - bstate.sent, bstate=newb,
                digest=digest, bank_impl=bank_impl, old_have=bstate.have,
                rejects=newf.rejects,
                rejects_delta=newf.rejects - fstate.rejects,
                quarantine_after=faults.quarantine_after,
            )
            return (new, newb, newf, key, tick_i + 1, stalled, done + 1,
                    metrics, ring)

        (dags, bstate, fstate, key, tick_i, _, done, metrics, ring) = (
            jax.lax.while_loop(
                cond, body,
                (dags, bstate, fstate, key, tick0, jnp.int32(0),
                 jnp.int32(0), metrics, ring),
            )
        )
        return (dags, bstate, fstate, key, tick_i, done,
                synced(dags, bstate, digest), metrics, ring)

    return jax.jit(converge)
