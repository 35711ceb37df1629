"""Compiled device programs for the mesh CEP engine.

The device NFA is a *settled-state bitmask automaton*: for the
bounded-partial pattern class (every stage positive with finite
``times``, loop stages ``consecutive()``, stage-to-stage contiguity
STRICT — see :func:`compile_device_pattern`) a partial match is fully
described by its per-stage take counts ``(c_0 .. c_s)``, and the number
of distinct count vectors is a small static constant ``Q`` of the
pattern alone.  Each key's live partials therefore pack into ONE int32
``alive`` bitmask (bit ``q`` = "a partial in settled state ``q`` is
live"), and one event advances ALL keys' NFAs with pure bit algebra —
no per-key host loop, no dynamic partial lists.

State ids are assigned in the host oracle's *candidate order* (depth
descending, then take/proceed path lexicographic with T < P — the order
``KeyNFA.advance`` walks its partials list, proven inductively against
``cep/nfa.py``), so emission order falls out of ascending bit order:
under ``SKIP_PAST_LAST_EVENT`` the winning match is the lowest set bit,
under ``NO_SKIP`` multiple completions on one event emit in bit order
with the virtual-start completion (bit ``Q``) last.  Bit-identity with
the host ``CepOperator`` — values AND emission order — is the contract
``tests/test_mesh_cep.py`` gates.

Event references ride ``ring`` planes: the last ``R = Σ max_i − 1``
processed event sequence numbers per key, shifted one step per event —
a live partial of depth ``d`` references exactly the ``d`` most recent
processed events (all-consecutive class), so a bounded ring IS the
SharedBuffer for this pattern class.  ``within`` gating stays x32-safe:
int64 timestamps never reach the device — the host packs, per
(key, event), a ``wok`` bitmask whose bit ``d−1`` says "a partial of
depth ``d`` is still inside the window at this event".

Program families, all cached in the shared tenancy
:data:`~flink_tpu.tenancy.program_cache.PROGRAM_CACHE`:

- **cep-advance**: keyed on ``(device ids, compiled pattern layout)`` —
  two engines running the same pattern shape on the same mesh share the
  executable (the multi-tenant zero-recompile contract; gated by
  ``tests/test_stateplane.py``'s CEP tier walk).  One ``lax.scan`` over
  the due-event axis, transitions unrolled over the ``Q`` settled
  states inside.
- **cep-prune**: the watermark ``within``-expiry scatter
  (``alive &= keep`` at slot cohorts).
- put / exchange-put / gather: the CEP planes are all-int32 ``[P,
  capacity]`` columns — exactly the join engines' plane shape — so the
  staging scatter, the fused keyBy exchange+scatter and the cohort
  gather reuse the ``join-put`` / ``join-exchange-put`` /
  ``join-gather`` families as-is (re-exported below).  Same executables,
  shared across tenants AND across engine kinds — the ROADMAP item-5
  direction (one state-plane kernel library) applied instead of a
  fourth hand-rolled copy.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flink_tpu.cep.pattern import (
    AfterMatchSkipStrategy,
    Contiguity,
    Pattern,
)
from flink_tpu.joins.kernels import (  # noqa: F401  (re-exported families)
    _mesh_key,
    build_join_exchange_put as build_cep_exchange_put,
    build_join_gather as build_cep_gather,
    build_join_put as build_cep_put,
)
from flink_tpu.parallel.mesh import KEY_AXIS, shard_map
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

#: bitmask budget: settled states live in one int32 ``alive`` plane and
#: the virtual-start completion needs one more match bit
MAX_STATES = 30
#: total take budget: ring depth R = Σ max_i − 1 rides int32 planes and
#: the ``wok`` window bitmask spends bit d−1 per live depth d
MAX_TOTAL_TAKES = 32


class UnsupportedCepPattern(ValueError):
    """The pattern is outside the device engine's bounded-partial class
    — the caller must fall back (LOUDLY) to the host ``CepOperator``."""


@dataclasses.dataclass(frozen=True)
class DevicePatternLayout:
    """The compiled pattern layout: the static transition tables of the
    settled-state automaton.  ``key`` (times × skip × within-gating) is
    the PROGRAM_CACHE component — everything else derives from it."""

    #: per-stage (min_times, max_times)
    times: Tuple[Tuple[int, int], ...]
    skip_past: bool
    has_within: bool
    #: settled states' count vectors, in candidate-rank order (= id)
    counts: Tuple[Tuple[int, ...], ...]
    #: per-state current stage / depth (= number of events taken)
    stage: Tuple[int, ...]
    depth: Tuple[int, ...]
    #: per-state successor bits (None = transition impossible)
    take_bit: Tuple[Optional[int], ...]
    proceed_bit: Tuple[Optional[int], ...]
    #: per-state "a take here completes the pattern"
    match_state: Tuple[bool, ...]
    #: the virtual start's successors / completion (single-stage case)
    v_take: Optional[int]
    v_proceed: Optional[int]
    v_match: bool
    #: ring planes: Σ max_i − 1 event-ref registers per key
    ring: int

    @property
    def n_states(self) -> int:
        return len(self.counts)

    @property
    def n_stages(self) -> int:
        return len(self.times)

    @property
    def key(self) -> Tuple:
        return (self.times, self.skip_past, self.has_within)

    def match_counts(self, bit: int) -> Tuple[int, ...]:
        """Final per-stage take counts of a completion on ``bit`` (the
        settled state's counts plus the completing take; bit
        ``n_states`` is the virtual single-event completion)."""
        if bit == self.n_states:
            return (1,)
        c = self.counts[bit]
        return c[:-1] + (c[-1] + 1,)


def _rank_path(counts: Tuple[int, ...]) -> str:
    """The candidate-order sort key: the partial's take/proceed history
    as a string with T='a' < P='b' — ``KeyNFA.advance`` appends a
    candidate's take-continuation before its proceed child, and deeper
    partials precede shallower ones in the partials list."""
    s = len(counts) - 1
    return "".join("a" * c + ("b" if i < s else "")
                   for i, c in enumerate(counts))


def compile_device_pattern(pattern: Pattern) -> DevicePatternLayout:
    """Compile ``pattern`` to the settled-state layout, or raise
    :class:`UnsupportedCepPattern` naming the first disqualifier.

    The device class — scoped honestly, not aspirationally: every
    stage positive (no notNext/notFollowedBy), finite ``times`` (no
    unbounded oneOrMore), loop stages ``consecutive()``, stage
    contiguity STRICT past the first stage, no until / greedy /
    combinations / iterative conditions, and skip strategy NO_SKIP or
    SKIP_PAST_LAST_EVENT.  Everything here keeps the partial-match set
    collapsible to one count vector per partial; each relaxation
    reintroduces combinatorial partials (which event subsets were
    skipped) that a fixed-width bitmask cannot carry — those patterns
    run on the host ``CepOperator``, loudly."""
    pattern = pattern.validate()
    stages = pattern.stages
    if not stages:
        raise UnsupportedCepPattern("empty pattern")
    for i, st in enumerate(stages):
        if st.negated:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: negative stages (notNext/"
                "notFollowedBy) need the host NFA's invalidation walk")
        if st.until_condition is not None:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: until() stop conditions")
        if st.iterative_condition is not None:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: iterative (match-context) "
                "conditions are per-partial, not columnar")
        if st.greedy:
            raise UnsupportedCepPattern(f"stage {st.name!r}: greedy()")
        if st.combinations:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: allowCombinations() makes the "
                "partial set combinatorial in skipped-event subsets")
        if st.max_times is None:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: unbounded oneOrMore/timesOrMore")
        if st.min_times < 1:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: optional stages")
        if i > 0 and st.contiguity is not Contiguity.STRICT:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: relaxed contiguity (followedBy) "
                "keeps ignored-event partials alive indefinitely")
        if st.max_times > 1 and not st.consecutive_internal:
            raise UnsupportedCepPattern(
                f"stage {st.name!r}: non-consecutive loop (times/"
                "oneOrMore without .consecutive())")
    d_total = sum(st.max_times for st in stages)
    if d_total > MAX_TOTAL_TAKES:
        raise UnsupportedCepPattern(
            f"pattern takes up to {d_total} events > {MAX_TOTAL_TAKES}"
            " (int32 ring/window budget)")

    times = tuple((int(st.min_times), int(st.max_times))
                  for st in stages)
    n = len(times)
    # enumerate the settled states: completed stages carry
    # c_i ∈ [min_i, max_i] (the proceed happened at a legal count);
    # the current stage carries c_s ∈ [1, max_s−1] when s == 0 (stage-0
    # partials exist only mid-loop) and c_s ∈ [0, max_s−1] otherwise
    # (count max_s is never STORED: the take at max either proceeds,
    # completes or dies — exactly the oracle's ``count+1 < max`` gate)
    states = []

    def _extend(s: int, prefix: Tuple[int, ...]) -> None:
        if len(prefix) == s:
            lo = 1 if s == 0 else 0
            for c in range(lo, times[s][1]):
                states.append(prefix + (c,))
            return
        i = len(prefix)
        for c in range(times[i][0], times[i][1] + 1):
            _extend(s, prefix + (c,))

    for s in range(n):
        _extend(s, ())
    states.sort(key=lambda c: (-sum(c), _rank_path(c)))
    if len(states) > MAX_STATES:
        raise UnsupportedCepPattern(
            f"{len(states)} settled states > {MAX_STATES} "
            "(int32 alive-bitmask budget)")
    sid = {c: q for q, c in enumerate(states)}

    take_bit, proceed_bit, match_state = [], [], []
    for c in states:
        s = len(c) - 1
        nc = c[-1] + 1
        take_bit.append(sid.get(c[:-1] + (nc,))
                        if nc < times[s][1] else None)
        proceed_bit.append(sid.get(c[:-1] + (nc, 0))
                           if (s + 1 < n and nc >= times[s][0])
                           else None)
        match_state.append(s == n - 1 and nc >= times[s][0])
    v_take = sid.get((1,)) if times[0][1] > 1 else None
    v_proceed = (sid.get((1, 0))
                 if (n > 1 and times[0][0] <= 1) else None)
    v_match = n == 1 and times[0][0] <= 1

    return DevicePatternLayout(
        times=times,
        skip_past=(pattern.skip
                   is AfterMatchSkipStrategy.SKIP_PAST_LAST_EVENT),
        has_within=pattern.within_ms is not None,
        counts=tuple(states),
        stage=tuple(len(c) - 1 for c in states),
        depth=tuple(sum(c) for c in states),
        take_bit=tuple(take_bit),
        proceed_bit=tuple(proceed_bit),
        match_state=tuple(match_state),
        v_take=v_take,
        v_proceed=v_proceed,
        v_match=v_match,
        ring=max(d_total - 1, 0),
    )


def build_cep_advance(mesh: Mesh, layout: DevicePatternLayout):
    """The batched NFA advance: gather each due key's state row,
    ``lax.scan`` its due events through the settled-state transition
    algebra, scatter the final state back and emit the per-event match
    bitmasks — every key's whole fire in ONE compiled program."""
    key = (_mesh_key(mesh), layout.key)
    return PROGRAM_CACHE.get_or_build(
        "cep-advance", key, lambda: _build_cep_advance(mesh, layout))


def _build_cep_advance(mesh: Mesh, layout: DevicePatternLayout):
    R = layout.ring
    n_state = 1 + R  # alive + ring planes
    Q = layout.n_states
    depth = layout.depth
    stage = layout.stage
    take_bit = layout.take_bit
    proceed_bit = layout.proceed_bit
    match_state = layout.match_state
    has_within = layout.has_within
    skip_past = layout.skip_past
    v_take, v_proceed, v_match = (layout.v_take, layout.v_proceed,
                                  layout.v_match)

    @partial(jax.jit, donate_argnums=(0,))
    def advance(state, pending, slots, idx, wok, nev):
        def local(*args):
            al = args[0][0]                       # [C] alive bitmask
            rings = [a[0] for a in args[1:n_state]]
            ph = args[n_state][0]                 # [PB] pending hits
            ps = args[n_state + 1][0]             # [PB] pending seqs
            s = args[n_state + 2][0]              # [K] slots
            ix = args[n_state + 3][0]             # [K, E] pending rows
            wk = args[n_state + 4][0]             # [K, E] window bits
            nv = args[n_state + 5][0]             # [K] due counts
            k_n, e_n = ix.shape
            h_ek = ph[ix].T                       # [E, K]
            q_ek = ps[ix].T
            w_ek = wk.T
            ok_ek = (jax.lax.broadcasted_iota(
                jnp.int32, (k_n, e_n), 1) < nv[:, None]).T

            def step(carry, xs):
                a, rs = carry[0], list(carry[1:])
                h, sq, w, ok = xs
                na = jnp.zeros_like(a)
                m = jnp.zeros_like(a)
                # unrolled over the Q settled states: every state dies
                # on a miss in this pattern class (STRICT + consecutive
                # — no ignore edges), so alive_next collects only
                # take/proceed successors
                for q in range(Q):
                    t = (a >> q) & 1
                    if has_within:
                        t = t & ((w >> (depth[q] - 1)) & 1)
                    t = t & ((h >> stage[q]) & 1)
                    if match_state[q]:
                        m = m | (t << q)
                    if take_bit[q] is not None:
                        na = na | (t << take_bit[q])
                    if proceed_bit[q] is not None:
                        na = na | (t << proceed_bit[q])
                # the virtual start candidate — walked LAST, as the
                # oracle does (bit Q for its single-event completion)
                hv = h & 1
                if v_match:
                    m = m | (hv << Q)
                if v_take is not None:
                    na = na | (hv << v_take)
                if v_proceed is not None:
                    na = na | (hv << v_proceed)
                if skip_past:
                    # the match consumed its events: every partial dies
                    # and the matched event starts nothing
                    na = jnp.where(m != 0, 0, na)
                nrs = rs[1:] + [sq] if R else []
                a = jnp.where(ok, na, a)
                rs = [jnp.where(ok, nr, r)
                      for nr, r in zip(nrs, rs)]
                return ((a, *rs), jnp.where(ok, m, 0))

            carry0 = (al[s], *[r[s] for r in rings])
            carry, m_seq = jax.lax.scan(
                step, carry0, (h_ek, q_ek, w_ek, ok_ek))
            a_f = carry[0]
            # padded lanes carry slot 0 with nev == 0: their carry is
            # the untouched row-0 value, so the scatter is a no-op
            al2 = al.at[s].set(a_f)
            rings2 = [r.at[s].set(f)
                      for r, f in zip(rings, carry[1:])]
            return (al2[None], *[r[None] for r in rings2],
                    m_seq.T[None], a_f[None])

        out = shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_state + 6),
            out_specs=(P(KEY_AXIS),) * (n_state + 2),
        )(*state, *pending, slots, idx, wok, nev)
        return out[:n_state], out[n_state], out[n_state + 1]

    return advance


def build_cep_prune(mesh: Mesh):
    """The watermark within-expiry: ``alive[p, slots] &= keep`` — one
    scatter over the resident cohort (keep bits host-computed from the
    per-depth window test; spilled keys prune lazily at reload)."""
    key = (_mesh_key(mesh),)
    return PROGRAM_CACHE.get_or_build(
        "cep-prune", key, lambda: _build_cep_prune(mesh))


def _build_cep_prune(mesh: Mesh):
    @partial(jax.jit, donate_argnums=(0,))
    def prune(alive, slots, keep):
        def local(al, s, k):
            # padded lanes carry slot 0 and keep == −1 (all ones)
            upd = al[0][s[0]] & k[0]
            return al.at[0, s[0]].set(upd)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * 3,
            out_specs=P(KEY_AXIS),
        )(alive, slots, keep)

    return prune
