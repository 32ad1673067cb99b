"""graftproto: exhaustive protocol model checking for the host protocols.

The durability and HA protocols rebuilt from the reference — the delta-
checkpoint chain with its background compactor (``checkpoint_delta.py``),
strict-seq serving hot-swap (``serving/registry.py apply_delta``), the
``DirtyTracker`` claim discipline (``dirty.py``), and the HA registry's
CREATING window under replica kills (``serving/ha.py``) — are concurrent
state machines whose bug class (torn tails, seq gaps, lost dirty marks,
mixed-version reads) hides in interleavings no example-based test
enumerates. This module is the fourth static-analysis leg beside
graftcheck/graftlint/graftrace: a small EXPLICIT-STATE model checker plus
faithful models of the shipped protocols (five today — the serving
lookup micro-batcher joined in the batched-serving round), explored
exhaustively.

Checker (stdlib-only, like :mod:`.concurrency`, so ``tools/graftproto.py``
loads it standalone):

* states are FLAT dicts of hashable values (ints, strs, tuples,
  frozensets) — frozen to sorted item-tuples for dedup;
* :class:`Action` = one named guarded atomic step of one process role;
  ``apply`` receives a fresh copy and returns one successor (mutate in
  place / return a dict) or several (return a list — nondeterministic
  outcomes like a write that may fail);
* :func:`check` runs BFS from the initial state with full state dedup, so
  the FIRST violation found has a minimal-length action trace;
* every invariant is checked at every reachable state; a state with no
  enabled action that ``is_done`` does not accept is a DEADLOCK;
* counterexamples pretty-print as an action trace with per-step state
  diffs (:func:`format_result`).

Model fidelity is the whole game, so the models are BRIDGED to the code
two ways: (1) every action carries the ``sync_point`` names
(``analysis/concurrency.py``) the real implementation emits at that
protocol step — :func:`missing_sync_points` greps the package source and
fails if a model references a point the code no longer has; (2)
:func:`trace_schedule` exports any explored trace (including every seeded
mutation's counterexample) as the ordered sync-point list a
``SerialSchedule``/``PointGate`` replay drives against the real
implementation (``tests/test_graftproto_replay.py``,
``tools/graftproto.py --emit-schedules``).

Scope and honesty — what is NOT modeled:

* multi-HOST elastic training (several trainers sharing one chain).
  Whole-process trainer crash + resume IS modeled now: the
  :func:`delta_chain` ``trainer_restart`` role (the graftchaos round)
  covers autosave -> SIGKILL -> ``fit(resume_from=)`` -> continue, with
  the resumed stream cursor re-derived from the committed manifest
  ``extra`` — closing the gap this section named since PR 11;
* unarmed (manifest-less) checkpoint directories — plain full dumps have
  no chain protocol to check (and the trainer_restart role accordingly
  treats a crash mid-full-save, before the re-arm, as unresumable);
* byte-level payload corruption beyond one torn tail per run (the
  ``tear`` budget), and chain/seq counts past the per-model bounds
  stated in each builder's docstring. Bounds are exhaustive WITHIN the
  budget, which is exactly the regime the hand-written interleaving
  tests sample one schedule of.

Two true positives surfaced while writing these models (both fixed in
the same PR, regression-tested in ``tests/test_delta_checkpoint.py``):
a full save over an armed chain re-armed with ``last_seq=0``, REUSING
burned seqs (serving replicas then ack the next real delta as stale and
silently stop updating — the :func:`delta_chain` ``full_save_resets_seq``
mutation is the pre-fix behavior), and ``applied_seq`` returned 0 after a
compaction emptied the chain (no content-version field in the manifest),
so freshly loaded serving models refused every subsequent delta as a gap
(the ``compact_zero_version`` mutation).
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from collections import deque
from typing import (Any, Callable, Dict, List, Optional,
                    Sequence, Tuple)

State = Dict[str, Any]
_CORRUPT = -99          # content marker: rows overwritten out of order


@dataclasses.dataclass(frozen=True)
class Action:
    """One named guarded atomic step of one process role.

    ``guard(state) -> bool`` reads a thawed state; ``apply(state)`` gets
    a FRESH copy it may mutate in place (return ``None``), replace
    (return a dict), or branch (return a list of dicts — each successor
    is labeled ``name#i``). ``syncs`` are the ``sync_point`` names the
    real implementation emits at this step (the model<->code bridge).

    Reduction metadata (all OPTIONAL — an action that declares nothing
    is treated maximally conservatively: it conflicts with everything,
    so partial-order reduction around it degrades to full expansion):

    * ``pc`` — the guard's program-counter conjuncts as ``(key, head)``
      pairs: the conjunct holds iff ``state[key] == head`` or
      ``state[key]`` is a tuple whose first element is ``head``
      (``"!head"`` negates). These are the structured part of the guard
      the ample rule can reason about: an action whose pc conjunct is
      false stays disabled until some explored action writes that key.
    * ``greads`` — DATA keys the guard reads beyond ``pc`` keys (and
      beyond ``dead``'s keys). Audited by :func:`audit_footprints`.
    * ``reads`` / ``writes`` — keys ``apply`` reads to compute its
      effect / may write. ``writes`` must be a superset of every
      reachable diff (audited); ``reads`` is the declared data
      dependency the independence relation uses.
    * ``dead(state)`` — a MONOTONE predicate: once true it stays true
      on every path (budget exhaustion). Dead actions are excluded
      from the ample rule's interference closure.
    """

    name: str
    role: str
    guard: Callable[[State], bool]
    apply: Callable[[State], Any]
    syncs: Tuple[str, ...] = ()
    pc: Tuple[Tuple[str, str], ...] = ()
    greads: Optional[frozenset] = None
    reads: Optional[frozenset] = None
    writes: Optional[frozenset] = None
    dead: Optional[Callable[[State], bool]] = None

    def __post_init__(self):
        for f in ("greads", "reads", "writes"):
            v = getattr(self, f)
            if v is not None and not isinstance(v, frozenset):
                object.__setattr__(self, f, frozenset(v))

    @property
    def declared(self) -> bool:
        """Full footprint declared — eligible for the ample rule."""
        return (self.greads is not None and self.reads is not None
                and self.writes is not None)

    def reads_all(self) -> frozenset:
        """Every key this action's guard or apply may read."""
        out = set(k for k, _h in self.pc)
        if self.greads:
            out |= self.greads
        if self.reads:
            out |= self.reads
        return frozenset(out)


def _pc_holds(state: State, key: str, head: str) -> bool:
    neg = head.startswith("!")
    if neg:
        head = head[1:]
    v = state[key]
    hit = (v == head) or (isinstance(v, tuple) and len(v) > 0
                          and v[0] == head)
    return hit != neg


@dataclasses.dataclass(frozen=True)
class Obligation:
    """Bounded-liveness obligation: from every reachable TRIGGER state
    — the states where ``after`` holds, or just the initial state when
    ``after`` is None — every maximal run must reach a state satisfying
    ``pred`` within ``within`` transitions.

    Checked by :func:`check_liveness` on the FULL (unreduced) graph —
    three counterexample shapes: a ``within``-step path that never
    satisfies ``pred`` (bound), a reachable cycle avoiding ``pred``
    (lasso — the run can postpone the eventuality forever), and a
    terminal state where the run simply ends without it.
    """

    name: str
    pred: Callable[[State], bool]
    within: int
    after: Optional[Callable[[State], bool]] = None


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    init: Tuple[Tuple[str, Any], ...]
    actions: Tuple[Action, ...]
    invariants: Tuple[Tuple[str, Callable[[State], bool]], ...]
    # accepting predicate for quiescent states: a state with NO enabled
    # action is a deadlock unless is_done(state)
    is_done: Callable[[State], bool]
    notes: str = ""
    # keys the invariants read (the ample rule's visibility set): an
    # action writing one of these may create or mask a violation, so it
    # never leads a reduced expansion. None = unknown = POR disabled.
    inv_reads: Optional[frozenset] = None
    # interchangeable process identities: groups of key-prefix /
    # identity-value names ((("h0","h1","h2"),) — states canonicalize
    # to the lexicographically smallest identity permutation before
    # dedup. Invariants/is_done MUST be symmetric under the permutation
    # (the cross_check harness is the empirical backstop).
    symmetry: Tuple[Tuple[str, ...], ...] = ()
    obligations: Tuple[Obligation, ...] = ()
    # monotone poison flags: inv-read keys written ONLY upward (bool
    # False->True, or frozenset growing) whose invariants fail exactly
    # when the flag is set. An action whose only inv-read writes are
    # such flags stays ample-eligible: on any deferred path the skipped
    # pre-states carry a SUBSET of the flags of their visited, shifted
    # counterparts, so every violation reachable there is still
    # reported (audit_footprints checks the upward-only discipline
    # dynamically; cross_check is the verdict-equality backstop).
    monotone_flags: frozenset = frozenset()
    # quiescent-payload collapse: (key, head) pairs declaring that once
    # ``state[key]`` is a tuple with this head, its payload elements are
    # dead — no guard, apply, or invariant ever reads past the head
    # again — so dedup may canonicalize the value to ``(head,)``.
    # Validated statically against the declared footprints (see
    # _collapse_problems); states merged this way are bisimilar, since
    # every read of the key in that head is head-only by construction.
    collapse: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        if self.inv_reads is not None \
                and not isinstance(self.inv_reads, frozenset):
            object.__setattr__(self, "inv_reads",
                               frozenset(self.inv_reads))
        if not isinstance(self.monotone_flags, frozenset):
            object.__setattr__(self, "monotone_flags",
                               frozenset(self.monotone_flags))

    def action(self, name: str) -> Action:
        for a in self.actions:
            if a.name == name:
                return a
        raise KeyError(name)


def make_model(name, init: State, actions, invariants, is_done,
               notes: str = "", inv_reads=None, symmetry=(),
               obligations=(), monotone_flags=(),
               collapse=()) -> Model:
    return Model(name=name, init=_freeze(init), actions=tuple(actions),
                 invariants=tuple(invariants), is_done=is_done,
                 notes=notes, inv_reads=inv_reads,
                 symmetry=tuple(tuple(g) for g in symmetry),
                 obligations=tuple(obligations),
                 monotone_flags=frozenset(monotone_flags),
                 collapse=tuple(tuple(c) for c in collapse))


@dataclasses.dataclass
class Counterexample:
    kind: str                      # "invariant" | "deadlock" | "error"
    invariant: str                 # violated invariant name (or "")
    trace: List[Tuple[str, State]]  # [("<init>", s0), (action, s1), ...]


@dataclasses.dataclass
class Result:
    model: str
    ok: bool
    complete: bool                 # frontier exhausted under max_states
    explored: int
    transitions: int
    elapsed_s: float
    counterexample: Optional[Counterexample] = None
    # reduction bookkeeping: {"reduce": bool, "ample": n states expanded
    # through a singleton ample set, "fused": n forced steps compressed,
    # "sym": n symmetry-canonicalization dedup hits}
    stats: Optional[Dict[str, Any]] = None


def _freeze(state: State) -> Tuple[Tuple[str, Any], ...]:
    """Flat dict of hashable values -> canonical hashable form. Raises
    on unhashable values — models must use ints/strs/tuples/frozensets,
    never lists/sets/dicts as values."""
    items = tuple(sorted(state.items()))
    hash(items)                    # fail fast on an unhashable value
    return items


# ---------------------------------------------------------------------------
# symmetry reduction: canonicalize under identity permutation
# ---------------------------------------------------------------------------

def _permutations(seq):
    if len(seq) <= 1:
        yield tuple(seq)
        return
    for i, head in enumerate(seq):
        for rest in _permutations(seq[:i] + seq[i + 1:]):
            yield (head,) + rest


def _sym_maps(symmetry) -> List[Dict[str, str]]:
    """Every identity-renaming map the symmetry groups generate (the
    cartesian product of each group's permutations)."""
    maps: List[Dict[str, str]] = [{}]
    for group in symmetry:
        nxt = []
        for perm in _permutations(tuple(group)):
            ren = dict(zip(group, perm))
            nxt.extend({**m, **ren} for m in maps)
        maps = nxt
    return maps


def _remap_value(v, ren):
    if isinstance(v, str):
        return ren.get(v, v)
    if isinstance(v, tuple):
        return tuple(_remap_value(x, ren) for x in v)
    if isinstance(v, frozenset):
        return frozenset(_remap_value(x, ren) for x in v)
    return v


def _remap_key(k: str, ren) -> str:
    if k in ren:
        return ren[k]
    head, sep, rest = k.partition("_")
    if sep and head in ren:
        return ren[head] + "_" + rest
    return k


def _canon(state: State, sym_maps) -> Tuple[Tuple[str, Any], ...]:
    """Freeze to the lexicographically-least form over every identity
    permutation: keys with a renamed ``<ident>_`` prefix move, and
    identity names appearing as values (including inside tuples and
    frozensets) are renamed consistently — so two states that differ
    only in which host plays which part dedup to one."""
    best = None
    best_key = None
    for ren in sym_maps:
        if ren:
            mapped = {_remap_key(k, ren): _remap_value(v, ren)
                      for k, v in state.items()}
        else:
            mapped = state
        frozen = _freeze(mapped)
        r = repr(frozen)           # total order over mixed value types
        if best is None or r < best_key:
            best, best_key = frozen, r
    return best


def _collapse_problems(model: Model) -> List[str]:
    """Statically validate the model's quiescent-payload ``collapse``
    declarations against the declared footprints. A ``(key, head)``
    collapse is sound when nothing can read past the head once the key
    carries it: the key is not an invariant read, and every action that
    reads the key's full value is pc-gated to a DIFFERENT head (so it
    is disabled — and stays disabled, every write produces a fresh
    value — in the collapsed head). ``is_done`` and guards validated
    here by the pc contract are head-only by construction;
    :func:`cross_check` is the end-to-end empirical backstop."""
    problems = []
    for key, head in model.collapse:
        if model.inv_reads is None:
            problems.append(f"collapse {key}/{head}: inv_reads unknown")
            continue
        if key in model.inv_reads:
            problems.append(
                f"collapse {key}/{head}: an invariant reads {key!r}")
        for a in model.actions:
            if not a.declared:
                problems.append(
                    f"collapse {key}/{head}: {a.name} has no declared "
                    f"footprint")
                continue
            if key not in (a.greads | a.reads):
                continue
            gated = any(k == key and not h.startswith("!") and h != head
                        for k, h in a.pc)
            if not gated:
                problems.append(
                    f"collapse {key}/{head}: {a.name} reads {key!r} "
                    f"without a pc gate on a different head")
    return problems


def _collapse_state(state: State, collapse) -> State:
    """Copy of ``state`` with every declared quiescent payload dropped
    (``(head, ...)`` -> ``(head,)``)."""
    out = dict(state)
    for key, head in collapse:
        v = out.get(key)
        if isinstance(v, tuple) and len(v) > 1 and v[0] == head:
            out[key] = (head,)
    return out


# ---------------------------------------------------------------------------
# partial-order reduction: SPIN-style singleton ample sets over declared
# footprints, with a dormancy closure for structured (pc-conjunct) guards
# ---------------------------------------------------------------------------

class _ReductionPlan:
    """Per-check() reduction tables for one model.

    The ample rule (documented inline below and in README): expanding
    ONLY action ``a`` at state ``s`` is sound when every action that
    could run before ``a`` on any full-graph path out of ``s`` is
    provably independent of ``a``, ``a`` cannot create or mask an
    invariant verdict the deferred actions would have exposed
    (visibility), and the reduced step does not close a cycle that
    would postpone the deferred actions forever (BFS proviso). Any
    doubt — an undeclared footprint, a guard the dormancy closure
    cannot bound, a nondeterministic candidate — falls back to full
    expansion.
    """

    def __init__(self, model: Model):
        self.acts = model.actions
        n = len(self.acts)
        self.n = n
        inv_reads = model.inv_reads
        # static per-action eligibility to LEAD an ample set: full
        # footprint declared + invisible (writes cannot touch any key
        # an invariant reads — so deferring other actions past it can
        # neither fabricate nor hide a verdict). Writes to declared
        # monotone poison flags are exempt: a flag only moves upward
        # and its invariant fails exactly when set, so the skipped
        # pre-states (subset flags) can only hide violations that the
        # visited, flag-applied states still report.
        self.eligible = []
        for a in self.acts:
            ok = a.declared and inv_reads is not None \
                and (a.writes & inv_reads) <= model.monotone_flags
            self.eligible.append(ok)
        self.por_on = inv_reads is not None and any(self.eligible)
        # static pairwise independence: a's effect and b's effect/guard
        # cannot interact in either order. Undeclared = conflicts.
        self.indep = [set() for _ in range(n)]
        for i, a in enumerate(self.acts):
            if not a.declared:
                continue
            ra = a.reads_all()
            for j, b in enumerate(self.acts):
                if i == j or not b.declared:
                    continue
                if not (a.writes & (b.writes | b.reads_all())) \
                        and not (ra & b.writes):
                    self.indep[i].add(j)
        self.pc_keys = [frozenset(k for k, _h in a.pc)
                        for a in self.acts]
        # ample decisions depend only on (enabled, dead, false-pc-
        # conjunct) masks — memoized across states
        self.cache: Dict[Any, int] = {}

    def _awake(self, ai: int, enabled: frozenset,
               dead: frozenset, false_pc) -> Optional[set]:
        """The interference closure: every action that could fire
        before candidate ``ai`` does on some full-graph path. Starts
        from the other enabled actions; a disabled action joins when
        the closure's writes could flip its false pc conjuncts (ALL of
        them — each must flip for the guard's structured part to hold)
        or, for a pc-satisfied-but-data-disabled action, its declared
        guard data reads. Unknown structure joins unconditionally."""
        A = set(enabled) - {ai}
        while True:
            W: set = set()
            unknown_w = False
            for j in A:
                wj = self.acts[j].writes
                if wj is None:
                    unknown_w = True
                    break
                W |= wj
            grew = False
            for c in range(self.n):
                if c == ai or c in A or c in dead or c in enabled:
                    continue
                fk = false_pc[c]
                if unknown_w:
                    join = True
                elif fk:
                    join = fk <= W
                else:
                    g = self.acts[c].greads
                    join = g is None or bool(g & W)
                if join:
                    A.add(c)
                    grew = True
            if not grew:
                return A

    def candidates(self, state: State, enabled_idx) -> Tuple[int, ...]:
        """All ample-singleton candidates for this state, in model
        action order (deterministic). Empty tuple means full expansion.
        The BFS tries them in order until one satisfies the queue
        proviso; any branches stored while probing a candidate that
        then fails the proviso are genuine successors (a superset of a
        sound ample set is itself sound), so no rollback is needed."""
        if not self.por_on or len(enabled_idx) < 2:
            return ()
        enabled = frozenset(enabled_idx)
        dead = frozenset(
            i for i, a in enumerate(self.acts)
            if a.dead is not None and i not in enabled and a.dead(state))
        false_pc = []
        for i, a in enumerate(self.acts):
            if i in enabled or i in dead or not a.pc:
                false_pc.append(frozenset())
                continue
            false_pc.append(frozenset(
                k for k, h in a.pc if not _pc_holds(state, k, h)))
        key = (enabled, dead, tuple(false_pc))
        hit = self.cache.get(key)
        if hit is not None:
            return hit
        picks = []
        for ai in enabled_idx:
            if not self.eligible[ai]:
                continue
            A = self._awake(ai, enabled, dead, false_pc)
            if _AMPLE_SKIP_DEPENDENCE or A <= self.indep[ai]:
                picks.append(ai)
        hit = tuple(picks)
        self.cache[key] = hit
        return hit

    def select(self, state: State, enabled_idx) -> Optional[int]:
        """First ample candidate, or None — used by chain fusion,
        which only follows a deterministic singleton anyway."""
        c = self.candidates(state, enabled_idx)
        return c[0] if c else None


# Negative-test seam (tests/test_graftproto.py): True disables the
# dependence check — the "naive" reduction that hides counterexamples.
# NEVER true outside the seeded POR-unsoundness test.
_AMPLE_SKIP_DEPENDENCE = False

# Bound + cycle guard for forced-sequence fusion (a chain of states
# with exactly one enabled deterministic action compresses into one
# stored state; every traversed state is still invariant-checked).
_FUSE_LIMIT = 64


def _violated(model: Model, state: State) -> Optional[str]:
    for name, pred in model.invariants:
        if not pred(state):
            return name
    return None


def _trace_of(parents, frozen) -> List[Tuple[str, State]]:
    steps = []
    cur = frozen
    while cur is not None:
        parent, label = parents[cur]
        steps.append((label or "<init>", dict(cur)))
        cur = parent
    steps.reverse()
    return steps


def _branches(action: Action, state: State) -> List[State]:
    """Apply one action to a copy of ``state`` under the Action.apply
    return contract (None = mutated in place, dict = replacement, list
    = nondeterministic branches)."""
    succ = dict(state)
    ret = action.apply(succ)
    if ret is None:
        return [succ]
    if isinstance(ret, dict):
        return [ret]
    return list(ret)


def _successors(model: Model, state: State):
    """Expand one thawed state FULLY: ``(enabled, [(label, succ), ...])``.

    The single home of the Action.apply return contract — check() (via
    :func:`_branches`) and sample_traces() both walk through here, so
    exported schedules can never diverge from what was checked.
    Branches of a nondeterministic action are labeled ``name#i``.
    """
    enabled = False
    out = []
    for action in model.actions:
        if not action.guard(state):
            continue
        enabled = True
        branches = _branches(action, state)
        for i, b in enumerate(branches):
            label = action.name if len(branches) == 1 \
                else f"{action.name}#{i}"
            out.append((label, b))
    return enabled, out


def check(model: Model, max_states: int = 500_000, *,
          reduce: bool = True, _rerun: bool = True) -> Result:
    """Exhaustive BFS over the model's reachable states.

    ``reduce=True`` (the default) enables the three sound reductions —
    symmetry canonicalization (models declaring ``symmetry``),
    singleton ample sets (models declaring action footprints +
    ``inv_reads``), and forced-sequence fusion (a run of states with
    exactly one enabled deterministic action stores only its endpoint;
    every traversed state is still invariant-checked) — and, on any
    counterexample, automatically re-runs unreduced so the reported
    trace is the minimal full-graph one. ``reduce=False`` is the plain
    PR-11 BFS: full expansion, every reachable state stored.

    Returns the first invariant violation or deadlock; ``complete=False``
    means the ``max_states`` budget cut exploration short (the CLI
    treats that as a failure for shipped models — an unexplored
    protocol is an unchecked one)."""
    t0 = time.perf_counter()
    sym_maps = _sym_maps(model.symmetry) \
        if (reduce and model.symmetry) else [{}]
    use_sym = len(sym_maps) > 1
    collapse = model.collapse if reduce else ()
    if collapse:
        bad_decl = _collapse_problems(model)
        if bad_decl:
            raise ValueError(f"{model.name}: unsound collapse "
                             f"declaration: {'; '.join(bad_decl)}")

    def canon(s: State):
        if collapse:
            s = _collapse_state(s, collapse)
        return _canon(s, sym_maps) if use_sym else _freeze(s)

    plan = _ReductionPlan(model) if reduce else None
    stats = {"reduce": reduce, "ample": 0, "fused": 0, "sym": 0}

    def finish(ok, complete, cex=None):
        return Result(model.name, ok, complete, explored, transitions,
                      time.perf_counter() - t0, cex, stats)

    def confirmed(cex_kind):
        """A counterexample under reduction: re-run the plain BFS so
        the user sees the minimal full-graph trace (and the reduced
        verdict is cross-confirmed). Falls back to the reduced trace if
        the full run cannot reproduce it inside the budget."""
        if not (reduce and _rerun):
            return None
        full = check(model, max_states, reduce=False, _rerun=False)
        if not full.ok:
            full.stats = dict(full.stats or {},
                              confirmed_reduced=True, **{
                                  k: v for k, v in stats.items()
                                  if k != "reduce"})
            return full
        return None

    f0 = canon(dict(model.init))
    parents: Dict[Any, Tuple[Any, Optional[str]]] = {f0: (None, None)}
    explored = 0
    transitions = 0
    bad = _violated(model, dict(f0))
    if bad is not None:
        return finish(False, True,
                      Counterexample("invariant", bad,
                                     _trace_of(parents, f0)))
    queue = deque([f0])
    closed = set()      # popped + expanded (the BFS queue proviso set)
    while queue:
        fs = queue.popleft()
        closed.add(fs)
        explored += 1
        state = dict(fs)
        enabled_idx = [i for i, a in enumerate(model.actions)
                       if a.guard(state)]
        if not enabled_idx:
            if not model.is_done(state):
                cex = Counterexample("deadlock", "",
                                     _trace_of(parents, fs))
                return confirmed("deadlock") or finish(False, True, cex)
            continue

        def process_edge(label: str, succ: State):
            """Store one successor, fusing forced chains first.

            A chain state with exactly one enabled deterministic action
            fuses unconditionally (nothing is deferred there). A chain
            state where the ample rule picks a deterministic singleton
            fuses too, with the BFS queue proviso guarding cycles: an
            endpoint hitting an OPEN stored state is safe (that state
            will still be expanded from the queue), but an endpoint
            hitting a CLOSED one could postpone the deferred actions
            around a cycle forever — then the state where the first
            ample fusion happened is stored instead, so its deferred
            actions get a full chance from the queue ("dedup_closed"
            when there was no ample fusion to roll back to: the caller
            must fall back itself if IT deferred anything).
            Returns ("stored"|"dedup"|"dedup_closed"|"done", result)."""
            nonlocal transitions
            cur, cur_label = succ, label
            chain_seen = set()
            pre_ample = None   # (frozen state, label) at first ample fuse
            transitions += 1
            while True:
                fcur = canon(cur)
                if fcur in parents:
                    if use_sym and fcur != _freeze(cur):
                        stats["sym"] += 1
                    if fcur not in closed:
                        return "dedup", None
                    if pre_ample is not None:
                        fpa, pa_label = pre_ample
                        parents[fpa] = (fs, pa_label)
                        if len(parents) >= max_states:
                            return "done", finish(True, False)
                        queue.append(fpa)
                        return "stored", None
                    return "dedup_closed", None
                bad = _violated(model, cur)
                if bad is not None:
                    parents[fcur] = (fs, cur_label)
                    cex = Counterexample("invariant", bad,
                                         _trace_of(parents, fcur))
                    return "done", (confirmed("invariant")
                                    or finish(False, True, cex))
                if plan is None:
                    break
                en = [i for i, a in enumerate(model.actions)
                      if a.guard(cur)]
                if not en:
                    break
                if len(en) == 1:
                    step = en[0]
                else:
                    step = plan.select(cur, en)
                    if step is None:
                        break
                nxt = _branches(model.actions[step], cur)
                if len(nxt) != 1:
                    break
                if fcur in chain_seen or len(chain_seen) >= _FUSE_LIMIT:
                    break
                if len(en) > 1 and pre_ample is None:
                    pre_ample = (fcur, cur_label)
                chain_seen.add(fcur)
                stats["fused"] += 1
                if len(en) > 1:
                    stats["ample"] += 1
                transitions += 1
                cur = nxt[0]
                cur_label = cur_label + "+" + model.actions[step].name
            parents[fcur] = (fs, cur_label)
            if len(parents) >= max_states:
                return "done", finish(True, False)
            queue.append(fcur)
            return "stored", None

        accepted = False
        for choice in (plan.candidates(state, enabled_idx)
                       if plan else ()):
            action = model.actions[choice]
            branches = _branches(action, state)
            all_safe = True
            for bi, b in enumerate(branches):
                label = action.name if len(branches) == 1 \
                    else f"{action.name}#{bi}"
                status, res = process_edge(label, b)
                if status == "done":
                    return res
                if status not in ("stored", "dedup"):
                    all_safe = False
            if all_safe:
                # ample accepted (every branch of the one chosen
                # action): the deferred actions re-appear, still
                # enabled, at each stored (or still-open deduped)
                # successor
                stats["ample"] += 1
                accepted = True
                break
            # some branch dedup-hit a CLOSED state = the BFS queue
            # proviso: taking only this ample step could postpone the
            # deferred actions around a cycle forever — try the next
            # candidate; branches already processed were genuine
            # successors (superset of a sound ample set = sound), and
            # with no candidate left, expand fully
        if accepted:
            continue
        for i in enabled_idx:
            action = model.actions[i]
            branches = _branches(action, state)
            for bi, b in enumerate(branches):
                label = action.name if len(branches) == 1 \
                    else f"{action.name}#{bi}"
                status, res = process_edge(label, b)
                if status == "done":
                    return res
    return finish(True, True)


def check_liveness(model: Model, max_states: int = 500_000) -> Result:
    """Check the model's bounded-liveness :class:`Obligation`s.

    Runs on the FULL (unreduced, uncanonicalized) graph: ample sets
    preserve safety, not eventualities — a reduced graph may drop
    exactly the postponing schedule an obligation exists to catch — so
    liveness obligations belong on models small enough to expand fully
    (the multi-host models are budgeted to stay so). For each
    obligation, every maximal run out of a trigger state must satisfy
    ``pred`` within ``within`` transitions; counterexamples are a
    ``within``-long avoiding path (bound), a reachable avoiding cycle
    (lasso), or a terminal avoiding state (the run just ends).
    """
    t0 = time.perf_counter()
    f0 = model.init
    parents: Dict[Any, Tuple[Any, Optional[str]]] = {f0: (None, None)}
    succs: Dict[Any, List[Tuple[str, Any]]] = {}
    queue = deque([f0])
    explored = 0
    transitions = 0
    while queue:
        fs = queue.popleft()
        explored += 1
        _en, out = _successors(model, dict(fs))
        edges = []
        for label, b in out:
            fb = _freeze(b)
            transitions += 1
            edges.append((label, fb))
            if fb not in parents:
                parents[fb] = (fs, label)
                if len(parents) >= max_states:
                    return Result(model.name, True, False, explored,
                                  transitions,
                                  time.perf_counter() - t0,
                                  stats={"liveness": "budget"})
                queue.append(fb)
        succs[fs] = edges

    def _cex(ob, trigger, path_edges, shape):
        # trace: init -> trigger via BFS parents, then the avoiding path
        trace = _trace_of(parents, trigger)
        for label, f in path_edges:
            trace.append((label, dict(f)))
        if trace:
            lab, st = trace[-1]
            trace[-1] = (f"{lab} ({shape})", st)
        return Result(model.name, False, True, explored, transitions,
                      time.perf_counter() - t0,
                      Counterexample("liveness", ob.name, trace),
                      stats={"liveness": shape})

    for ob in model.obligations:
        if ob.after is None:
            triggers = [f0] if not ob.pred(dict(f0)) else []
        else:
            triggers = [f for f in succs
                        if ob.after(dict(f)) and not ob.pred(dict(f))]
        # BFS the pred-avoiding subgraph from every trigger at once:
        # depth = transitions taken while avoiding pred
        depth: Dict[Any, int] = {}
        back: Dict[Any, Tuple[Any, str]] = {}
        trig_of: Dict[Any, Any] = {}
        dq = deque()
        for t in triggers:
            if t not in depth:
                depth[t] = 0
                trig_of[t] = t
                dq.append(t)

        def _avoid_path(end):
            edges = []
            cur = end
            while cur in back:
                prev, label = back[cur]
                edges.append((label, cur))
                cur = prev
            edges.reverse()
            return trig_of.get(end, cur), edges

        while dq:
            f = dq.popleft()
            d = depth[f]
            out = succs.get(f, [])
            if not out:
                trig, edges = _avoid_path(f)
                return _cex(ob, trig, edges, "run ends")
            for label, fb in out:
                if ob.pred(dict(fb)):
                    continue
                if fb in depth:
                    continue           # cycles handled by DFS below
                depth[fb] = d + 1
                back[fb] = (f, label)
                trig_of[fb] = trig_of[f]
                if d + 1 >= ob.within:
                    trig, edges = _avoid_path(fb)
                    return _cex(ob, trig, edges, "bound")
                dq.append(fb)
        # lasso: any cycle inside the avoiding subgraph (states in
        # `depth` whose avoiding successors stay in `depth`)
        color: Dict[Any, int] = {}
        for start in depth:
            if color.get(start):
                continue
            stack = [(start, iter(succs.get(start, [])))]
            color[start] = 1
            while stack:
                f, it = stack[-1]
                adv = False
                for label, fb in it:
                    if fb not in depth:
                        continue
                    c = color.get(fb, 0)
                    if c == 1:
                        trig, edges = _avoid_path(f)
                        edges.append((label, fb))
                        return _cex(ob, trig, edges, "lasso")
                    if c == 0:
                        color[fb] = 1
                        stack.append((fb, iter(succs.get(fb, []))))
                        adv = True
                        break
                if not adv:
                    color[f] = 2
                    stack.pop()
    return Result(model.name, True, True, explored, transitions,
                  time.perf_counter() - t0,
                  stats={"liveness": "ok",
                         "obligations": len(model.obligations)})


def cross_check(model: Model, max_states: int = 500_000) -> Dict[str, Any]:
    """The reduction soundness harness: check the model reduced and
    unreduced, assert the verdicts agree exactly (ok/kind/invariant),
    and report the state reduction. Raises AssertionError on any
    divergence — this is what the weekly CI lane and the tests run over
    every shipped model."""
    red = check(model, max_states, reduce=True)
    full = check(model, max_states, reduce=False)
    assert red.complete and full.complete, \
        f"[{model.name}] budget cut: reduced={red.complete} " \
        f"full={full.complete}"
    assert red.ok == full.ok, \
        f"[{model.name}] verdict diverged: reduced ok={red.ok} " \
        f"full ok={full.ok}"
    if not red.ok:
        rk = (red.counterexample.kind, red.counterexample.invariant)
        fk = (full.counterexample.kind, full.counterexample.invariant)
        assert rk == fk, \
            f"[{model.name}] counterexample diverged: {rk} vs {fk}"
    assert red.explored <= full.explored, \
        f"[{model.name}] reduction EXPANDED the graph: " \
        f"{red.explored} > {full.explored}"
    return {"model": model.name, "reduced": red, "full": full,
            "ratio": (full.explored / red.explored
                      if red.explored else 1.0)}


class _TracingState(dict):
    """Records which keys a guard actually reads — the footprint audit."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.reads: set = set()

    def __getitem__(self, k):
        self.reads.add(k)
        return super().__getitem__(k)

    def get(self, k, default=None):
        self.reads.add(k)
        return super().get(k, default)


def audit_footprints(model: Model, max_states: int = 4_000) -> List[str]:
    """Empirically validate declared reduction metadata against up to
    ``max_states`` reachable states: a guard must read only
    ``pc``/``greads`` keys, an apply's observed diff must stay inside
    declared ``writes``, and a ``dead`` action must be disabled.
    Returns human-readable violations (tests assert it returns none).
    ``reads`` (apply's data reads) is the one declaration the audit
    must trust — :func:`cross_check` is its empirical backstop."""
    problems: List[str] = list(_collapse_problems(model))
    seen = {model.init}
    queue = deque([model.init])
    audited = 0
    while queue and audited < max_states:
        fs = queue.popleft()
        state = dict(fs)
        audited += 1
        for action in model.actions:
            if action.dead is not None and action.dead(state):
                if action.guard(state):
                    problems.append(
                        f"{model.name}.{action.name}: dead(s) true but "
                        f"guard(s) true — dead is not a disabledness "
                        f"witness")
                continue
            if action.greads is not None:
                ts = _TracingState(state)
                enabled = action.guard(ts)
                allowed = set(k for k, _h in action.pc) | action.greads
                extra = ts.reads - allowed
                if extra:
                    problems.append(
                        f"{model.name}.{action.name}: guard read "
                        f"undeclared keys {sorted(extra)}")
            else:
                enabled = action.guard(state)
            if not enabled:
                if action.pc and all(_pc_holds(state, k, h)
                                     for k, h in action.pc) \
                        and action.greads is not None \
                        and not action.greads:
                    problems.append(
                        f"{model.name}.{action.name}: disabled with all "
                        f"pc conjuncts true and no declared data reads")
                continue
            if action.pc and not all(_pc_holds(state, k, h)
                                     for k, h in action.pc):
                problems.append(
                    f"{model.name}.{action.name}: enabled with a false "
                    f"pc conjunct — pc is not part of the guard")
            if action.writes is not None:
                for b in _branches(action, state):
                    diff = {k for k in set(state) | set(b)
                            if state.get(k, _CORRUPT) is not
                            b.get(k, _CORRUPT)
                            and state.get(k) != b.get(k)}
                    extra = diff - action.writes
                    if extra:
                        problems.append(
                            f"{model.name}.{action.name}: wrote "
                            f"undeclared keys {sorted(extra)}")
                    for k in diff & model.monotone_flags:
                        old, new = state.get(k), b.get(k)
                        up = (old is False and new is True) \
                            or (isinstance(old, frozenset)
                                and isinstance(new, frozenset)
                                and old <= new)
                        if not up:
                            problems.append(
                                f"{model.name}.{action.name}: monotone "
                                f"flag {k!r} moved downward "
                                f"({old!r} -> {new!r})")
        if model.inv_reads is not None:
            ts = _TracingState(state)
            for name, pred in model.invariants:
                pred(ts)
            extra = ts.reads - model.inv_reads
            if extra:
                problems.append(
                    f"{model.name}: invariants read undeclared keys "
                    f"{sorted(extra)} (inv_reads incomplete)")
        for _label, b in _successors(model, state)[1]:
            fb = _freeze(b)
            if fb not in seen:
                seen.add(fb)
                queue.append(fb)
    return sorted(set(problems))


def format_result(res: Result, model: Optional[Model] = None) -> str:
    """Human-readable verdict; counterexamples print the minimal action
    trace with per-step state diffs (and each action's sync points, so
    the trace reads as a replayable schedule)."""
    head = (f"[{res.model}] explored {res.explored} states / "
            f"{res.transitions} transitions in {res.elapsed_s:.2f}s")
    if res.ok and res.complete:
        return head + " — all invariants hold, no deadlock"
    if res.ok:
        return head + f" — INCOMPLETE (state budget hit)"
    cex = res.counterexample
    if cex.kind == "deadlock":
        what = "DEADLOCK (no enabled action, not an accepting state)"
    elif cex.kind == "liveness":
        what = f"LIVENESS OBLIGATION VIOLATED: {cex.invariant}"
    else:
        what = f"INVARIANT VIOLATED: {cex.invariant}"
    lines = [head + f" — {what}", "  counterexample "
             f"({len(cex.trace) - 1} steps):"]
    prev: State = {}
    for label, state in cex.trace:
        if label == "<init>":
            lines.append("    <init>")
            prev = state
            continue
        diff = [f"{k}: {prev.get(k)!r}->{v!r}"
                for k, v in sorted(state.items()) if prev.get(k) != v]
        syncs = ""
        if model is not None:
            base = label.split("#", 1)[0]
            try:
                pts = model.action(base).syncs
            except KeyError:
                pts = ()
            if pts:
                syncs = f"  [sync: {', '.join(pts)}]"
        lines.append(f"    {label}{syncs}  {{{'; '.join(diff)}}}")
        prev = state
    return "\n".join(lines)


def trace_schedule(model: Model,
                   trace: Sequence[Tuple[str, State]]) -> List[str]:
    """Flatten one action trace into the ordered ``sync_point`` list a
    SerialSchedule/PointGate replay drives against the real code."""
    out: List[str] = []
    for label, _state in trace:
        if label == "<init>":
            continue
        base = label.split("#", 1)[0]
        try:
            out.extend(model.action(base).syncs)
        except KeyError:
            pass
    return out


def model_sync_points(model: Model) -> List[str]:
    out = sorted({p for a in model.actions for p in a.syncs})
    return out


# Design-only sync points: protocol steps the multi-host models pin
# down BEFORE the implementation lands (ROADMAP item 3 is models-first
# by mandate). Each name is the contract the implementing PR must emit
# at that step; missing_sync_points treats them as reserved rather than
# drifted, and `tools/graftproto --check-sync` reports them separately
# so they cannot silently rot into vocabulary nobody implements.
RESERVED_SYNC_POINTS = frozenset({
    # multi-host delta round: per-host shard-local write acknowledged
    # to the coordinator; coordinator verifies ALL payloads before the
    # single cross-host manifest commit
    "ckpt.multihost.ack",
    "ckpt.multihost.verify",
    # elastic membership: worker join/leave announcement and the
    # failure detector's sweep that orphans a dead worker's shards
    "train.member.join",
    "train.member.detect",
    # N->M reshard through the checkpoint path: one row-range handoff
    # (source release only after destination apply)
    "reshard.row.apply",
    "reshard.row.release",
})


def reserved_sync_points(model: Model) -> List[str]:
    """The subset of a model's sync points that are design-only
    (reserved for the implementing PR) rather than emitted today."""
    return [p for p in model_sync_points(model)
            if p in RESERVED_SYNC_POINTS]


def missing_sync_points(model: Model,
                        package_root: Optional[str] = None) -> List[str]:
    """Sync points a model references that the package source does not
    emit — the fidelity tripwire: a refactor that renames or drops a
    ``sync_point`` invalidates the model, and this makes that loud.
    Reserved (design-only) points are excluded; ``reserved_sync_points``
    lists those."""
    if package_root is None:
        package_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
    have = set()
    for root, _dirs, names in os.walk(package_root):
        if "__pycache__" in root:
            continue
        for n in names:
            if not n.endswith(".py"):
                continue
            with open(os.path.join(root, n), "r", encoding="utf-8") as fh:
                have.update(re.findall(r'sync_point\(\s*[fr]?"([^"]+)"',
                                       fh.read()))
    return [p for p in model_sync_points(model)
            if p not in have and p not in RESERVED_SYNC_POINTS]


# ---------------------------------------------------------------------------
# Model 1: serving hot-swap (registry.apply_delta vs snapshotting readers)
# ---------------------------------------------------------------------------

def hot_swap(*, seq_gate: bool = True, atomic_publish: bool = True,
             max_seq: int = 3, readers: int = 2) -> Model:
    """``ModelRegistry.apply_delta`` strict seq gating against concurrent
    snapshotting lookups (``ServingModel.lookup``).

    Two variables (vA, vB) stand for the per-variable rows one delta
    patches; the published model is the triple (vA, vB, version) and
    ``applied`` is the set of delta seqs whose rows the served states
    contain. Deltas 1..max_seq are all in flight at once (a retrying
    publisher can present any of them in any order, stale and gapped
    included). Readers snapshot the published pair then read it — the
    one-reference-grab discipline of ``ServingModel.lookup``.

    Invariants: readers never observe a mixed version; ``applied_seq``
    is monotone; a model at version v serves exactly the deltas
    ``{1..v}`` (a dropped gate silently loses the skipped delta's rows).

    Mutations: ``seq_gate=False`` removes the gap refusal (the seeded
    ``drop_seq_gate``); ``atomic_publish=False`` patches the two
    variables in place in two steps instead of building functionally and
    publishing one reference under the lock.
    """
    init: State = {"version": 0, "vA": 0, "vB": 0,
                   "applied": frozenset(), "pending":
                   frozenset(range(1, max_seq + 1)),
                   "build": 0, "monotone_ok": True,
                   "redeliver_left": 1}
    for i in range(readers):
        init[f"r{i}_pc"] = "idle"
        init[f"r{i}_snap"] = (0, 0)

    actions: List[Action] = []

    def redeliver(seq):
        # a retrying publisher re-presents an ALREADY-applied delta
        # (network retry / replica catch-up overlap) — this is what
        # makes the stale-ack branch reachable at all
        def guard(s):
            return s["redeliver_left"] > 0 and seq <= s["version"] \
                and seq not in s["pending"]

        def apply(s):
            s["redeliver_left"] -= 1
            s["pending"] = s["pending"] | {seq}
        return Action(f"redeliver({seq})", "publisher", guard, apply)

    def ack_stale(seq):
        def guard(s):
            return seq in s["pending"] and seq <= s["version"] \
                and s["build"] == 0

        def apply(s):
            s["pending"] = s["pending"] - {seq}
        # the real stale path returns BEFORE any swap sync point: only
        # find_model's registry.find fires (registry.py apply_delta)
        return Action(f"ack_stale({seq})", "applier", guard, apply,
                      syncs=("registry.find",))

    def publish(s, seq):
        if seq < s["version"]:
            s["monotone_ok"] = False
        s["vA"] = s["vB"] = s["version"] = seq
        s["applied"] = s["applied"] | {seq}
        s["pending"] = s["pending"] - {seq}

    def apply_next(seq):
        def guard(s):
            return seq in s["pending"] and seq == s["version"] + 1 \
                and s["build"] == 0

        if atomic_publish:
            def apply(s):
                publish(s, seq)
            return Action(f"apply({seq})", "applier", guard, apply,
                          syncs=("registry.find",
                                 "registry.swap.build",
                                 "registry.swap.commit"))

        def apply_start(s):
            s["build"] = seq
            s["vA"] = seq              # first variable patched IN PLACE
        start = Action(f"apply_start({seq})", "applier", guard,
                       apply_start, syncs=("registry.find",
                                           "registry.swap.build"))

        def fin_guard(s):
            return s["build"] == seq

        def apply_finish(s):
            s["build"] = 0
            publish(s, seq)
        finish = Action(f"apply_finish({seq})", "applier", fin_guard,
                        apply_finish, syncs=("registry.swap.commit",))
        return [start, finish]

    def apply_gapped(seq):
        # the dropped gate: any pending newer seq applies directly
        def guard(s):
            return seq in s["pending"] and seq > s["version"] + 1 \
                and s["build"] == 0

        def apply(s):
            publish(s, seq)
        return Action(f"apply_gapped({seq})", "applier", guard, apply,
                      syncs=("registry.find",
                             "registry.swap.build",
                             "registry.swap.commit"))

    for seq in range(1, max_seq + 1):
        actions.append(redeliver(seq))
        actions.append(ack_stale(seq))
        nxt = apply_next(seq)
        actions.extend(nxt if isinstance(nxt, list) else [nxt])
        if not seq_gate:
            actions.append(apply_gapped(seq))

    for i in range(readers):
        def snap_guard(s, i=i):
            return s[f"r{i}_pc"] == "idle"

        def snap_apply(s, i=i):
            s[f"r{i}_pc"] = "reading"
            s[f"r{i}_snap"] = (s["vA"], s["vB"])
        actions.append(Action(f"r{i}_snapshot", f"reader{i}", snap_guard,
                              snap_apply,
                              syncs=("serving.lookup.snapshot",)))

        def read_guard(s, i=i):
            return s[f"r{i}_pc"] == "reading"

        def read_apply(s, i=i):
            s[f"r{i}_pc"] = "idle"
            s[f"r{i}_snap"] = (0, 0)
        actions.append(Action(f"r{i}_read", f"reader{i}", read_guard,
                              read_apply, syncs=("registry.find",)))

    def inv_consistent(s):
        return all(s[f"r{i}_snap"][0] == s[f"r{i}_snap"][1]
                   for i in range(readers))

    def inv_no_lost(s):
        return s["applied"] == frozenset(range(1, s["version"] + 1))

    def inv_monotone(s):
        return s["monotone_ok"]

    def is_done(s):
        return not s["pending"] and s["build"] == 0 \
            and all(s[f"r{i}_pc"] == "idle" for i in range(readers))

    return make_model(
        "hot_swap", init, actions,
        [("reader_sees_one_version", inv_consistent),
         ("version_covers_exactly_applied_deltas", inv_no_lost),
         ("applied_seq_monotone", inv_monotone)],
        is_done,
        notes="registry.apply_delta seq gate + one-reference-swap vs "
              "snapshotting ServingModel.lookup readers",
        # the readers are interchangeable lookups: nothing distinguishes
        # which thread plays which, so states differing only in the
        # reader permutation canonicalize to one
        symmetry=(tuple(f"r{i}" for i in range(readers)),))


# ---------------------------------------------------------------------------
# Model 2: DirtyTracker claim discipline (dirty.py + save_delta's writer)
# ---------------------------------------------------------------------------

def dirty_tracker(*, restore_on_failure: bool = True, chunks: int = 2,
                  marks: int = 3) -> Model:
    """``DirtyTracker.snapshot_clear``/``restore`` claims under
    concurrent ``mark_dirty`` and a failing writer (``save_delta``'s
    claim/commit/restore protocol around ``ckpt.delta.commit``).

    Per chunk, ``pend`` counts change epochs (a mark bumps it), ``cov``
    the highest epoch a COMMITTED save chain covers. The saver claims
    the dirty set atomically (``snapshot_clear``), writes (which may
    fail), then commits or restores the claim.

    Invariant (the one that matters for durability): no dirty chunk is
    ever lost to a completed save chain — at every state, a chunk with
    uncovered changes is either still marked dirty or claimed by the
    in-flight writer whose claim covers those changes.

    Mutation: ``restore_on_failure=False`` drops the claim restore on a
    failed write (the seeded ``skip_claim_restore``) — the chunk's
    changes vanish from both the bitmap and the chain.
    """
    init: State = {
        "pend": (0,) * chunks, "cov": (0,) * chunks,
        "dirty": (False,) * chunks,
        "claim": None,            # tuple per chunk: claimed epoch | None
        "saver": "idle",          # idle | claimed | written | failed
        "marks_left": marks,
    }

    def _set(t, i, v):
        return t[:i] + (v,) + t[i + 1:]

    actions: List[Action] = []

    def mark(c):
        def guard(s):
            return s["marks_left"] > 0 and s["pend"][c] < 2

        def apply(s):
            s["pend"] = _set(s["pend"], c, s["pend"][c] + 1)
            s["dirty"] = _set(s["dirty"], c, True)
            s["marks_left"] -= 1
        return Action(f"mark({c})", "trainer", guard, apply,
                      syncs=("dirty.mark",))

    for c in range(chunks):
        actions.append(mark(c))

    def snap_guard(s):
        return s["saver"] == "idle" and any(s["dirty"])

    def snap_apply(s):
        s["claim"] = tuple(s["pend"][c] if s["dirty"][c] else None
                           for c in range(chunks))
        s["dirty"] = (False,) * chunks
        s["saver"] = "claimed"
    actions.append(Action("snapshot_clear", "saver", snap_guard,
                          snap_apply, syncs=("dirty.snapshot",)))

    def write_guard(s):
        return s["saver"] == "claimed"

    def write_apply(s):
        ok = dict(s, saver="written")
        fail = dict(s, saver="failed")
        return [ok, fail]
    actions.append(Action("write", "saver", write_guard, write_apply,
                          syncs=("ckpt.delta.write",)))

    def commit_guard(s):
        return s["saver"] == "written"

    def commit_apply(s):
        s["cov"] = tuple(max(s["cov"][c], s["claim"][c] or 0)
                         for c in range(chunks))
        s["claim"] = None
        s["saver"] = "idle"
    actions.append(Action("commit", "saver", commit_guard, commit_apply,
                          syncs=("ckpt.delta.commit",)))

    def fail_guard(s):
        return s["saver"] == "failed"

    def restore_apply(s):
        if restore_on_failure:
            s["dirty"] = tuple(s["dirty"][c] or s["claim"][c] is not None
                               for c in range(chunks))
        s["claim"] = None
        s["saver"] = "idle"
    actions.append(Action("restore", "saver", fail_guard, restore_apply,
                          syncs=("dirty.restore",)))

    def inv_no_lost(s):
        for c in range(len(s["pend"])):
            bound = s["cov"][c]
            if s["claim"] is not None and s["claim"][c] is not None:
                bound = max(bound, s["claim"][c])
            if s["pend"][c] > bound and not s["dirty"][c]:
                return False
        return True

    def is_done(s):
        return s["saver"] == "idle" and s["claim"] is None

    return make_model(
        "dirty_tracker", init, actions,
        [("no_dirty_chunk_lost_to_completed_chain", inv_no_lost)],
        is_done,
        notes="DirtyTracker snapshot_clear/restore claims vs concurrent "
              "mark_dirty and a failing delta writer")


# ---------------------------------------------------------------------------
# Model 3: HA registry load / CREATING window with replica kill
# ---------------------------------------------------------------------------

def ha_registry(*, atomic_commit: bool = True, kills: int = 1,
                serves: int = 2) -> Model:
    """The serving registry's async-load CREATING window (``create_model``
    -> loader thread -> one-lock commit), a failover routing client, and
    a killer SIGKILLing replicas (``serving/ha.py``).

    Two replicas serve one model sign. r0 boots with the model NORMAL
    (the ``--load`` path); r1 restores from a living peer's catalog
    (``restore_from_peers``: only NORMAL entries restore — a CREATING
    peer is polled, modeled as the guard). A killed replica loses
    everything and respawns through restore-from-peer, or from the dump
    when no peer serves (the ``--load``/URI fallback), so the system
    always recovers. The client rotates over replicas like
    ``RoutingClient._rotate``.

    Invariants: NORMAL status implies the model object is installed
    (status and install commit under ONE lock hold — the reader-visible
    pair can never be half-published); a lookup is served only from an
    installed NORMAL model (no CREATING/partial model ever serves rows).

    Mutation: ``atomic_commit=False`` publishes status=NORMAL one step
    before installing the model object — ``find_model`` then hands a
    lookup a missing/partial model inside the window.
    """
    R = ("r0", "r1")
    init: State = {"kill_left": kills, "serves_left": serves,
                   "cl": "idle", "cl_tried": frozenset(),
                   "served_uninstalled": False}
    init.update({"r0_alive": True, "r0_status": "normal",
                 "r0_inst": True, "r0_boot": 0,
                 "r1_alive": True, "r1_status": "absent",
                 "r1_inst": False, "r1_boot": 1})

    actions: List[Action] = []

    def peer_of(r):
        return "r1" if r == "r0" else "r0"

    def restore_start(r):
        # restore_from_peers: a living peer serves NORMAL -> re-create
        def guard(s):
            p = peer_of(r)
            return s[f"{r}_alive"] and s[f"{r}_status"] == "absent" \
                and s[f"{p}_alive"] and s[f"{p}_status"] == "normal"

        def apply(s):
            s[f"{r}_status"] = "creating"
        return Action(f"{r}_restore_start", r, guard, apply,
                      syncs=("ha.restore.model", "registry.load.start"))

    def boot_load(r):
        # the dump-URI path: available even with no living peer
        def guard(s):
            p = peer_of(r)
            no_peer = not (s[f"{p}_alive"]
                           and s[f"{p}_status"] == "normal")
            return s[f"{r}_alive"] and s[f"{r}_status"] == "absent" \
                and s[f"{r}_boot"] > 0 and no_peer

        def apply(s):
            s[f"{r}_boot"] -= 1
            s[f"{r}_status"] = "creating"
        return Action(f"{r}_boot_load", r, guard, apply,
                      syncs=("registry.load.start",))

    def load_commit(r):
        def guard(s):
            return s[f"{r}_alive"] and s[f"{r}_status"] == "creating"

        if atomic_commit:
            def apply(s):
                s[f"{r}_inst"] = True
                s[f"{r}_status"] = "normal"
            return [Action(f"{r}_load_commit", r, guard, apply,
                           syncs=("registry.load.commit",))]

        def apply_status(s):
            s[f"{r}_status"] = "normal"    # published BEFORE the install
        first = Action(f"{r}_commit_status", r, guard, apply_status,
                       syncs=("registry.load.commit",))

        def inst_guard(s):
            return s[f"{r}_alive"] and s[f"{r}_status"] == "normal" \
                and not s[f"{r}_inst"]

        def apply_inst(s):
            s[f"{r}_inst"] = True
        second = Action(f"{r}_install", r, inst_guard, apply_inst)
        return [first, second]

    def kill(r):
        def guard(s):
            # any alive replica may die; liveness is preserved not by a
            # guard here but by respawn() plus each replica's dump-URI
            # boot budget — a respawned replica with no NORMAL peer
            # boot-loads, so the state space has no stranded deadlock
            return s["kill_left"] > 0 and s[f"{r}_alive"]

        def apply(s):
            s["kill_left"] -= 1
            s[f"{r}_alive"] = False
            s[f"{r}_status"] = "absent"
            s[f"{r}_inst"] = False
        return Action(f"kill({r})", "chaos", guard, apply)

    def respawn(r):
        def guard(s):
            return not s[f"{r}_alive"]

        def apply(s):
            s[f"{r}_alive"] = True
        return Action(f"respawn({r})", "chaos", guard, apply,
                      syncs=("ha.restore.catalog",))

    for r in R:
        actions.append(restore_start(r))
        actions.append(boot_load(r))
        actions.extend(load_commit(r))
        actions.append(kill(r))
        actions.append(respawn(r))

    # client: rotate over untried replicas; serve from a NORMAL one
    def try_replica(r):
        def guard(s):
            return s["serves_left"] > 0 and s["cl"] == "idle" \
                and r not in s["cl_tried"]

        def apply(s):
            if s[f"{r}_alive"] and s[f"{r}_status"] == "normal":
                # served: record AT THE SERVE INSTANT whether find_model
                # handed out an uninstalled model (the lookup keeps its
                # reference afterwards — a later kill cannot corrupt it,
                # so this is a point check, not a lingering predicate)
                s["cl"] = f"served:{r}"
                if not s[f"{r}_inst"]:
                    s["served_uninstalled"] = True
            else:
                s["cl_tried"] = s["cl_tried"] | {r}
        return Action(f"cl_try({r})", "client", guard, apply,
                      syncs=("routing.attempt", "registry.find"))

    def served_done(r):
        def guard(s):
            return s["cl"] == f"served:{r}"

        def apply(s):
            s["cl"] = "idle"
            s["cl_tried"] = frozenset()
            s["serves_left"] -= 1
        return Action(f"cl_done({r})", "client", guard, apply,
                      syncs=("serving.lookup.snapshot",))

    def all_failed_guard(s):
        return s["cl"] == "idle" and s["cl_tried"] == frozenset(R)

    def all_failed_apply(s):
        # every replica bounced: the caller sees the error and retries
        s["cl_tried"] = frozenset()
    for r in R:
        actions.append(try_replica(r))
        actions.append(served_done(r))
    actions.append(Action("cl_all_failed", "client", all_failed_guard,
                          all_failed_apply))

    def inv_normal_installed(s):
        return all(not (s[f"{r}_alive"] and s[f"{r}_status"] == "normal")
                   or s[f"{r}_inst"] for r in R)

    def inv_served_installed(s):
        return not s["served_uninstalled"]

    def is_done(s):
        return s["serves_left"] == 0

    return make_model(
        "ha_registry", init, actions,
        [("normal_status_implies_model_installed", inv_normal_installed),
         ("lookup_served_only_from_installed_model", inv_served_installed)],
        is_done,
        notes="create_model CREATING window + restore_from_peers + "
              "RoutingClient rotation under replica SIGKILL")


# ---------------------------------------------------------------------------
# Model 4: delta-checkpoint chain (writer, manifest commit, compactor,
# crash-at-any-step, torn tails, loads racing everything)
# ---------------------------------------------------------------------------

def delta_chain(*, commit_order: str = "payload_first",
                carry_seq_on_full: bool = True,
                compact_content_seq: bool = True,
                resume_cursor: str = "exact",
                max_seq: int = 3, fulls: int = 1, crashes: int = 1,
                tears: int = 1, loads: int = 1,
                trainer_steps: int = 3,
                trainer_crashes: int = 1) -> Model:
    """The ``checkpoint_delta.py`` chain protocol end to end.

    One variable whose base is TWO field files (weights + a slot — the
    granularity at which the compactor folds and a crash interleaves).
    Content versions count as "reflects committed deltas <= v";
    applying a delta whose seq is neither idempotent (<= v) nor the
    successor (v+1) poisons the field (``_CORRUPT`` — rows from the
    wrong epoch overwrote newer rows), which is exactly what replaying
    a stale chain over a half-new base does.

    Protocol steps modeled 1:1 with the code: delta save = write the
    payload file, then commit the manifest (``ckpt.delta.commit``, the
    one atomic rename); full save = reset_chain FIRST, write the two
    base fields, then re-arm (``ckpt.full.reset``/``ckpt.full.arm``),
    carrying ``last_seq`` so burned seqs are never reused; the
    background compactor (never concurrent with the saver —
    ``join_compactor``) folds verified entries field-by-field, commits
    a fresh manifest (new base_id, ``last_seq`` preserved,
    ``content_seq`` = folded content), then GCs the chain; a crash
    budget kills the writer/compactor thread between any two steps; a
    tear budget corrupts the FINAL committed payload (the dying-disk
    case); the loader snapshots the manifest, reads fields and chain
    files in any interleaving, drops a bad FINAL entry, errors on a bad
    middle, and retries once when ``base_id`` moved under it — the
    ``load_checkpoint`` retry loop.

    Invariants (checked at every reachable state):

    * ``load_is_committed_consistent`` — a PUBLISHED load is never
      mixed/corrupt and equals a content version that was actually
      committed ("a load never observes a mid-chain tear as success";
      "torn FINAL recovers to the last complete delta");
    * ``no_silent_commit_loss`` — a load only ever drops a committed
      entry whose payload a TEAR destroyed, never one whose payload
      simply was not written yet;
    * ``seqs_never_reused`` — burned seqs never reappear;
    * ``load_version_matches_content`` — the version a load reports
      (``applied_seq``) equals the content it loaded (the serving
      hot-swap gate depends on this).

    The ``trainer_restart`` role (the elastic-recovery round): the
    trainer is the process every other role lives inside. It consumes
    stream batches 1..``trainer_steps`` in order (``Trainer.fit``'s
    loop; ``t_hi`` = the highest step whose rows its in-memory state
    holds, ``t_next`` = the stream cursor), and every delta/full save
    records the cursor at its commit (``save_delta(extra=...)`` — the
    manifest channel ``fit(autosave_every=)`` writes). A whole-process
    crash (``trainer_crashes`` budget, distinct from the thread-level
    ``crashes``) kills the saver AND compactor mid-anything; restore
    (``fit(resume_from=)`` -> ``load_checkpoint`` + ``ShardStream``
    ``skip_batches``) re-derives both the state and the stream position
    from the last COMMITTED manifest entry the load verifies — a torn
    tail resumes one autosave earlier, exactly like the load does.

    Invariant ``trainer_neither_reapplies_nor_skips_rows``: every batch
    the (possibly resumed) trainer applies is the successor of its
    in-memory content — it never re-applies a step whose rows the
    restored checkpoint already holds and never skips one (the
    bit-identical-resume contract).

    Mutations: ``commit_order="manifest_first"`` commits the manifest
    before the payload (seeded ``manifest_before_payload``);
    ``carry_seq_on_full=False`` re-arms full saves at ``last_seq=0``
    (seq reuse; pre-fix shipped behavior); ``compact_content_seq=False``
    drops the compacted manifest's content version (``applied_seq``
    reports 0; also pre-fix shipped behavior);
    ``resume_cursor="zero"`` restores the model state but re-reads the
    stream from position zero (the dead-reader/naive-restart behavior
    the ``ShardStream.skip_batches`` contract exists to prevent —
    seeded ``resume_cursor_from_zero``), ``resume_cursor="skip"``
    resumes one batch past the cursor (an off-by-one skip — seeded
    ``resume_cursor_skips_a_step``).

    Bounds: ``max_seq`` deltas, one full save, one crash, one tear, one
    load (with one retry), ``trainer_steps`` stream batches, one
    whole-process trainer crash, compaction past 2 chain entries —
    exhaustive within the budgets: 65,054 states reduced (the default
    gate) / 90,726 fully expanded at the defaults, down from the
    141,649 the PR-16 encoding cost plain BFS (footprint-driven payload
    hygiene + quiescent-payload collapse + ample fusion).
    """
    if resume_cursor not in ("exact", "zero", "skip"):
        raise ValueError(f"resume_cursor must be exact|zero|skip, "
                         f"got {resume_cursor!r}")
    init: State = {
        # manifest: None | (gen, last_seq, content_seq, chain tuple)
        "mf": (0, 0, 0, ()),
        "gen_next": 1,
        # ((seq, "ok"|"torn"), ...): payloads some manifest commit has
        # referenced. Uncommitted payloads live in "orphans" until
        # delta_commit moves them — the split keys the footprints need
        # to see that an in-flight write is invisible to every chain
        # reader (loads, restores, the compactor) until its commit.
        "files": (),
        "orphans": (),
        "f0": 0, "f1": 0,     # base field content versions
        "saver": ("idle",),
        "comp": ("off",),
        "loader": ("off",),
        "burned": frozenset(), "reused": False,
        # monitor key: the loader's publish step evaluates the three
        # load invariants ITSELF and poisons this set with the violated
        # names. Invariants then read ONLY {bad, reused, t_flag} —
        # which is what makes the loader's pc-stepping actions
        # invisible to the ample rule (the PR-18 reduction refactor;
        # verdicts are unchanged because the flags are written by the
        # same atomic step that used to create the "done" tuple the
        # old predicates inspected, and only ever grow)
        "bad": frozenset(),
        "truths": frozenset([0]),
        "crash_left": crashes, "tear_left": tears,
        "full_left": fulls, "load_left": loads, "retry_left": 1,
        # trainer_restart role: program counter, in-memory content
        # high-water step, stream cursor, committed-cursor bookkeeping
        # (seq -> cursor pairs mirror the manifest ``extra`` channel;
        # base_cursor is what a chainless manifest's base reflects)
        "t_pc": "run", "t_hi": 0, "t_next": 1,
        "t_crash_left": trainer_crashes, "t_flag": False,
        "cursors": (), "base_cursor": 0,
    }

    def files_get(s, seq):
        for q, st in s["files"]:
            if q == seq:
                return st
        return None

    def files_set(s, seq, st, key="files"):
        rest = tuple((q, x) for q, x in s[key] if q != seq)
        s[key] = tuple(sorted(rest + ((seq, st),)))

    def apply_seq(content, seq):
        """Newest-wins row overwrite of one delta over one field."""
        if content == _CORRUPT:
            return _CORRUPT
        if seq <= content:
            return content             # idempotent re-apply
        if seq == content + 1:
            return seq
        return _CORRUPT                # gap: rows from the wrong epoch

    def live(s):
        # the trainer's in-memory content = every committed delta
        return max(s["burned"], default=0)

    def committed_cursor(s):
        """Stream cursor the last committed manifest entry records
        (the ``extra`` channel) — the base's when the chain is empty."""
        return s["cursors"][-1][1] if s["cursors"] else s["base_cursor"]

    actions: List[Action] = []

    # -- delta save ---------------------------------------------------------
    def dw_guard(s):
        # the saver belongs to the trainer's process (fit's autosave:
        # the snapshot on the step thread, the write on its writer
        # thread): no save from a dead process, and no empty delta —
        # a save needs rows the last commit does not cover
        return s["mf"] is not None and s["saver"] == ("idle",) \
            and s["comp"] == ("off",) and s["mf"][1] < max_seq \
            and s["t_pc"] == "run" and s["t_hi"] > committed_cursor(s)

    def commit_seq(s, seq):
        gen, _last, cseq, chain = s["mf"]
        if seq in s["burned"]:
            s["reused"] = True
        s["burned"] = s["burned"] | {seq}
        s["mf"] = (gen, seq, cseq, chain + (seq,))
        s["truths"] = s["truths"] | {seq}
        # the manifest entry's extra records the trainer cursor at the
        # save's SNAPSHOT. fit trains on while its writer thread
        # commits, but what the save holds (rows and cursor) was fixed
        # when the snapshot was dispatched; the model keeps the save
        # atomic against trainer_step, which is that instant
        s["cursors"] = s["cursors"] + ((seq, s["t_hi"]),)

    def write_branches(s, seq, key):
        """A payload lands whole, or — tear budget — torn: fs.open_atomic
        fsyncs file and directory, so a file ever observed whole can
        never tear LATER; the torn-from-birth branch models the
        dying-disk partial rename the PR-8 recovery lane exists for
        (the writer computed its crc from memory and never re-reads,
        so the commit can still follow a torn payload)."""
        ok = dict(s)
        files_set(ok, seq, "ok", key)
        ok["saver"] = ("dw", seq)
        out = [ok]
        if s["tear_left"] > 0:
            torn = dict(s)
            files_set(torn, seq, "torn", key)
            torn["tear_left"] -= 1
            torn["saver"] = ("dw", seq)
            out.append(torn)
        return out

    _dw_pc = (("saver", "idle"), ("comp", "off"), ("t_pc", "run"))
    _dw_greads = ("mf", "t_hi", "cursors", "base_cursor")
    if commit_order == "payload_first":
        def dw_apply(s):
            # the payload lands as an ORPHAN: no manifest references it
            # until delta_commit, so no chain reader can observe it —
            # which is exactly what the split files/orphans footprint
            # lets the ample rule exploit
            return write_branches(s, s["mf"][1] + 1, "orphans")
        actions.append(Action("delta_write", "saver", dw_guard, dw_apply,
                              syncs=("ckpt.delta.write",),
                              pc=_dw_pc, greads=_dw_greads,
                              reads=("mf", "orphans", "tear_left"),
                              writes=("orphans", "tear_left", "saver")))

        def dc_guard(s):
            return s["saver"][0] == "dw"

        def dc_apply(s):
            seq = s["saver"][1]
            # the commit publishes the orphan: the manifest now
            # references it, so it moves into the committed set
            st = None
            for q, x in s["orphans"]:
                if q == seq:
                    st = x
            s["orphans"] = tuple((q, x) for q, x in s["orphans"]
                                 if q != seq)
            if st is not None:
                files_set(s, seq, st)
            commit_seq(s, seq)
            s["saver"] = ("idle",)
        actions.append(Action("delta_commit", "saver", dc_guard,
                              dc_apply, syncs=("ckpt.delta.commit",),
                              pc=(("saver", "dw"),), greads=(),
                              reads=("saver", "mf", "burned", "truths",
                                     "cursors", "t_hi", "orphans",
                                     "files"),
                              writes=("mf", "burned", "reused",
                                      "truths", "cursors", "saver",
                                      "files", "orphans")))
    else:                              # mutated: manifest before payload
        def dce_apply(s):
            seq = s["mf"][1] + 1
            commit_seq(s, seq)
            s["saver"] = ("dw", seq)
        actions.append(Action("delta_commit_early", "saver", dw_guard,
                              dce_apply, syncs=("ckpt.delta.commit",),
                              pc=_dw_pc, greads=_dw_greads,
                              reads=("mf", "burned", "truths",
                                     "cursors", "t_hi"),
                              writes=("mf", "burned", "reused",
                                      "truths", "cursors", "saver")))

        def dwl_guard(s):
            return s["saver"][0] == "dw"

        def dwl_apply(s):
            # mutated order: the manifest ALREADY references this seq,
            # so the late payload is committed the instant it lands
            out = write_branches(s, s["saver"][1], "files")
            for b in out:
                b["saver"] = ("idle",)
            return out
        actions.append(Action("delta_write_late", "saver", dwl_guard,
                              dwl_apply, syncs=("ckpt.delta.write",),
                              pc=(("saver", "dw"),), greads=(),
                              reads=("saver", "files", "tear_left"),
                              writes=("files", "tear_left", "saver")))

    def crash_saver_guard(s):
        return s["saver"] != ("idle",) and s["crash_left"] > 0

    def crash_saver_apply(s):
        # the writer thread dies between steps: an uncommitted payload
        # stays an orphan (GC'd later, never read); a committed-but-
        # unwritten one stays MISSING — the mutated order's poison
        s["saver"] = ("idle",)
        s["crash_left"] -= 1
    actions.append(Action("crash_saver", "chaos", crash_saver_guard,
                          crash_saver_apply,
                          pc=(("saver", "!idle"),),
                          greads=("crash_left",), reads=(),
                          writes=("saver", "crash_left"),
                          dead=lambda s: s["crash_left"] == 0))

    # -- full save ----------------------------------------------------------
    def fs_guard(s):
        return s["saver"] == ("idle",) and s["comp"] == ("off",) \
            and s["full_left"] > 0 and s["mf"] is not None \
            and s["t_pc"] == "run"

    def fs_reset_apply(s):
        carried = s["mf"][1] if carry_seq_on_full else 0
        s["mf"] = None
        s["files"] = ()            # reset_chain GCs every delta file
        s["orphans"] = ()          # ... and every uncommitted payload
        s["cursors"] = ()          # the chain entries' extras go with it
        s["full_left"] -= 1
        # the dump will hold every in-memory row: capture the cursor
        # the re-armed manifest records (t_hi frozen — blocking save)
        s["saver"] = ("fr", carried, s["t_hi"])
    actions.append(Action("full_reset_chain", "saver", fs_guard,
                          fs_reset_apply, syncs=("ckpt.full.reset",),
                          pc=(("saver", "idle"), ("comp", "off"),
                              ("t_pc", "run")),
                          greads=("full_left", "mf"),
                          reads=("mf", "t_hi"),
                          writes=("mf", "files", "orphans", "cursors",
                                  "full_left", "saver"),
                          dead=lambda s: s["full_left"] == 0))

    def fw0_guard(s):
        return s["saver"][0] == "fr"

    def fw0_apply(s):
        s["f0"] = live(s)
        s["saver"] = ("f0",) + s["saver"][1:]
    actions.append(Action("full_write_f0", "saver", fw0_guard, fw0_apply,
                          syncs=("ckpt.writer.run",),
                          pc=(("saver", "fr"),), greads=(),
                          reads=("saver", "burned"),
                          writes=("f0", "saver")))

    def fw1_guard(s):
        return s["saver"][0] == "f0"

    def fw1_apply(s):
        s["f1"] = live(s)
        s["saver"] = ("f1",) + s["saver"][1:]
    actions.append(Action("full_write_f1", "saver", fw1_guard, fw1_apply,
                          syncs=("ckpt.writer.run",),
                          pc=(("saver", "f0"),), greads=(),
                          reads=("saver", "burned"),
                          writes=("f1", "saver")))

    def fa_guard(s):
        return s["saver"][0] == "f1"

    def fa_apply(s):
        carried = s["saver"][1]
        s["mf"] = (s["gen_next"], carried, carried, ())
        s["gen_next"] += 1
        s["base_cursor"] = s["saver"][2]
        s["saver"] = ("idle",)
    actions.append(Action("full_arm", "saver", fa_guard, fa_apply,
                          syncs=("ckpt.full.arm",),
                          pc=(("saver", "f1"),), greads=(),
                          reads=("saver", "gen_next"),
                          writes=("mf", "gen_next", "base_cursor",
                                  "saver")))

    # -- background compactor ----------------------------------------------
    def verified_tail(s):
        """Last verified chain seq (bad FINAL dropped), or None when a
        bad MIDDLE makes the chain unfoldable/unloadable."""
        chain = s["mf"][3]
        tail = None
        for i, seq in enumerate(chain):
            if files_get(s, seq) == "ok":
                tail = seq
            elif i == len(chain) - 1:
                return tail            # bad final: fold/load the prefix
            else:
                return None            # bad middle
        return tail

    def comp_start_guard(s):
        # the compactor REFUSES a chain that does not fully verify
        # (true positive found by this model: folding around a torn
        # committed entry and GC'ing it converts the documented loud
        # mid-chain refusal into silent permanent data loss — the torn
        # delta's chunks were already claim-cleared, nothing re-covers
        # them; checkpoint_delta._compact_impl now aborts instead)
        chain = s["mf"][3] if s["mf"] is not None else ()
        return s["comp"] == ("off",) and s["saver"] == ("idle",) \
            and s["t_pc"] == "run" \
            and len(chain) >= 2 and verified_tail(s) == chain[-1]

    def comp_start_apply(s):
        s["comp"] = ("run", verified_tail(s))
    actions.append(Action("compact_start", "compactor", comp_start_guard,
                          comp_start_apply, syncs=("ckpt.compact.run",),
                          pc=(("comp", "off"), ("saver", "idle"),
                              ("t_pc", "run")),
                          greads=("mf", "files"),
                          reads=("mf", "files"), writes=("comp",)))

    def fold_field(s, field, upto):
        v = s[field]
        for seq in s["mf"][3]:
            if seq > upto:
                break
            if files_get(s, seq) == "ok":
                v = apply_seq(v, seq)
        s[field] = v

    def comp_fold0_guard(s):
        return s["comp"][0] == "run"

    def comp_fold0_apply(s):
        fold_field(s, "f0", s["comp"][1])
        s["comp"] = ("c0", s["comp"][1])
    actions.append(Action("compact_fold_f0", "compactor",
                          comp_fold0_guard, comp_fold0_apply,
                          pc=(("comp", "run"),), greads=(),
                          reads=("comp", "mf", "files", "f0"),
                          writes=("f0", "comp")))

    def comp_fold1_guard(s):
        return s["comp"][0] == "c0"

    def comp_fold1_apply(s):
        fold_field(s, "f1", s["comp"][1])
        s["comp"] = ("c1", s["comp"][1])
    actions.append(Action("compact_fold_f1", "compactor",
                          comp_fold1_guard, comp_fold1_apply,
                          pc=(("comp", "c0"),), greads=(),
                          reads=("comp", "mf", "files", "f1"),
                          writes=("f1", "comp")))

    def comp_commit_guard(s):
        return s["comp"][0] == "c1"

    def comp_commit_apply(s):
        folded = s["comp"][1]
        cseq = folded if compact_content_seq else 0
        s["mf"] = (s["gen_next"], s["mf"][1], cseq, ())
        s["gen_next"] += 1
        # the folded base now reflects the folded tail's cursor; the
        # chain (and its per-entry extras) is gone
        s["base_cursor"] = dict(s["cursors"]).get(folded,
                                                  s["base_cursor"])
        s["cursors"] = ()
        s["comp"] = ("gc",)
    actions.append(Action("compact_commit", "compactor",
                          comp_commit_guard, comp_commit_apply,
                          syncs=("ckpt.compact.commit",),
                          pc=(("comp", "c1"),), greads=(),
                          reads=("comp", "mf", "gen_next", "cursors",
                                 "base_cursor"),
                          writes=("mf", "gen_next", "base_cursor",
                                  "cursors", "comp")))

    def comp_gc_guard(s):
        return s["comp"] == ("gc",)

    def comp_gc_apply(s):
        # everything the folded manifest no longer references goes —
        # committed chain payloads and crash orphans alike (no payload
        # can be mid-commit here: delta saves are disabled while the
        # compactor runs)
        s["files"] = ()
        s["orphans"] = ()
        s["comp"] = ("off",)
    actions.append(Action("compact_gc", "compactor", comp_gc_guard,
                          comp_gc_apply,
                          pc=(("comp", "gc"),), greads=(), reads=(),
                          writes=("files", "orphans", "comp")))

    def crash_comp_guard(s):
        return s["comp"] != ("off",) and s["crash_left"] > 0

    def crash_comp_apply(s):
        # fields may be partially folded under the OLD manifest — replay
        # idempotence must make any later load correct anyway
        s["comp"] = ("off",)
        s["crash_left"] -= 1
    actions.append(Action("crash_compactor", "chaos", crash_comp_guard,
                          crash_comp_apply,
                          pc=(("comp", "!off"),),
                          greads=("crash_left",), reads=(),
                          writes=("comp", "crash_left"),
                          dead=lambda s: s["crash_left"] == 0))

    # -- trainer_restart role ----------------------------------------------
    def t_step_guard(s):
        # fit's loop: one batch at a time. Steps do run while the writer
        # thread commits a save; none of them reaches that save (its
        # content is its snapshot's), so the model orders each save
        # whole between two steps
        return s["t_pc"] == "run" and s["saver"] == ("idle",) \
            and s["t_next"] <= trainer_steps

    def t_step_apply(s):
        k = s["t_next"]
        if k <= s["t_hi"] or k > s["t_hi"] + 1:
            # the batch is not the successor of the in-memory content:
            # a re-applied committed step (k <= t_hi) or a skipped one
            s["t_flag"] = True
        s["t_hi"] = max(s["t_hi"], k)
        s["t_next"] = k + 1
    actions.append(Action("trainer_step", "trainer", t_step_guard,
                          t_step_apply, syncs=("trainer.fit.step",),
                          pc=(("t_pc", "run"), ("saver", "idle")),
                          greads=("t_next",),
                          reads=("t_next", "t_hi"),
                          writes=("t_flag", "t_hi", "t_next")))

    def t_crash_guard(s):
        return s["t_pc"] == "run" and s["t_crash_left"] > 0

    def t_crash_apply(s):
        # whole-PROCESS death (SIGKILL at any sync point): the saver
        # and the background compactor die with it — uncommitted
        # payloads stay orphans, a mid-full-save dir stays unarmed, a
        # mid-fold compactor leaves partially-folded fields under the
        # old manifest. In-memory rows past the last commit are gone.
        s["t_crash_left"] -= 1
        s["t_pc"] = "dead"
        s["saver"] = ("idle",)
        s["comp"] = ("off",)
    actions.append(Action("trainer_crash", "chaos", t_crash_guard,
                          t_crash_apply,
                          pc=(("t_pc", "run"),),
                          greads=("t_crash_left",), reads=(),
                          writes=("t_crash_left", "t_pc", "saver",
                                  "comp"),
                          dead=lambda s: s["t_crash_left"] == 0))

    def t_loadable(s):
        # what load_checkpoint accepts: every non-final chain entry
        # verifies (a bad FINAL is dropped whole, a bad middle raises)
        chain = s["mf"][3]
        return all(files_get(s, q) == "ok" for q in chain[:-1])

    def t_restore_guard(s):
        # fit(resume_from=): a committed manifest must exist and load —
        # a crash mid-full-save (mf None) has nothing to resume from
        # and the dead trainer is an accepted end state
        return s["t_pc"] == "dead" and s["mf"] is not None \
            and t_loadable(s)

    def t_restore_apply(s):
        # the restored content and the stream cursor BOTH come from the
        # entry the load actually applies: a torn tail resumes one
        # autosave earlier, exactly like the load recovers
        tail = verified_tail(s)
        cur = (dict(s["cursors"]).get(tail, s["base_cursor"])
               if tail is not None else s["base_cursor"])
        s["t_pc"] = "run"
        s["t_hi"] = cur
        if resume_cursor == "exact":
            s["t_next"] = cur + 1
        elif resume_cursor == "zero":
            s["t_next"] = 1            # naive restart: stream from 0
        else:
            s["t_next"] = cur + 2      # off-by-one: skips a batch
    actions.append(Action("trainer_restore", "trainer", t_restore_guard,
                          t_restore_apply,
                          syncs=("trainer.resume.restore",),
                          pc=(("t_pc", "dead"),),
                          greads=("mf", "files"),
                          reads=("mf", "files", "cursors",
                                 "base_cursor"),
                          writes=("t_pc", "t_hi", "t_next")))

    # -- loader -------------------------------------------------------------
    def lm_guard(s):
        return s["loader"] == ("off",) and s["load_left"] > 0 \
            and s["mf"] is not None

    def lm_apply(s):
        # only the generation survives to the outcome: load_checkpoint
        # re-reads the manifest AFTER the field streams (see
        # load_read_chain), so the first read contributes nothing but
        # the base_id the finish-time coherence check compares
        s["load_left"] -= 1
        s["loader"] = ("mf", s["mf"][0])
    actions.append(Action("load_read_manifest", "loader", lm_guard,
                          lm_apply, syncs=("registry.load.start",),
                          pc=(("loader", "off"),),
                          greads=("load_left", "mf"),
                          reads=("mf", "load_left"),
                          writes=("load_left", "loader"),
                          dead=lambda s: (s["load_left"] == 0
                                          and s["retry_left"] == 0)))

    def lf0_guard(s):
        return s["loader"][0] == "mf"

    def lf0_apply(s):
        s["loader"] = ("lf0",) + s["loader"][1:] + (s["f0"],)
    actions.append(Action("load_read_f0", "loader", lf0_guard, lf0_apply,
                          pc=(("loader", "mf"),), greads=(),
                          reads=("loader", "f0"), writes=("loader",)))

    def lf1_guard(s):
        return s["loader"][0] == "lf0"

    def lf1_apply(s):
        s["loader"] = ("lf1",) + s["loader"][1:] + (s["f1"],)
    actions.append(Action("load_read_f1", "loader", lf1_guard, lf1_apply,
                          pc=(("loader", "lf0"),), greads=(),
                          reads=("loader", "f1"), writes=("loader",)))

    def lc_guard(s):
        return s["loader"][0] == "lf1"

    def lc_apply(s):
        # the replay re-reads the manifest AFTER the base fields
        # (load_checkpoint line order: fields stream first, then
        # read_manifest -> replay_chain) — together with newest-wins
        # idempotence this is what makes loads racing a mid-fold
        # compactor converge instead of publishing a mixed base; the
        # version is computed from the SAME verify pass the replay
        # performs (the registry version-coherence fix this PR)
        _pc, gen0, v0, v1 = s["loader"]
        if s["mf"] is None:
            # manifest vanished (racing full-save reset): no replay;
            # the base_id check at finish forces the retry
            s["loader"] = ("fin", gen0, 0, v0, v1, False)
            return
        chain = s["mf"][3]
        cseq = s["mf"][2]
        tail = None
        missing_drop = False
        bad_middle = False
        for i, seq in enumerate(chain):
            st = files_get(s, seq)
            if st == "ok":
                v0 = apply_seq(v0, seq)
                v1 = apply_seq(v1, seq)
                tail = seq
            elif i == len(chain) - 1:
                # verify_chain: bad FINAL entry discarded whole
                missing_drop = st is None
            else:
                bad_middle = True       # refuse: later deltas build on it
                break
        if bad_middle:
            s["loader"] = ("cerr", gen0)
        else:
            version = tail if tail is not None else cseq
            s["loader"] = ("fin", gen0, version, v0, v1, missing_drop)
    actions.append(Action("load_read_chain", "loader", lc_guard,
                          lc_apply,
                          pc=(("loader", "lf1"),), greads=(),
                          reads=("loader", "mf", "files"),
                          writes=("loader",)))

    def _retry(s, gen0):
        cur_gen = s["mf"][0] if s["mf"] is not None else -1
        if cur_gen != gen0 and s["retry_left"] > 0:
            s["retry_left"] -= 1
            s["load_left"] += 1
            s["loader"] = ("off",)
            return True
        return False

    def lfin_guard(s):
        return s["loader"][0] == "fin"

    def lfin_apply(s):
        _pc, gen0, version, v0, v1, miss = s["loader"]
        cur_gen = s["mf"][0] if s["mf"] is not None else -1
        if cur_gen != gen0:
            if not _retry(s, gen0):
                s["loader"] = ("err",)
            return
        s["loader"] = ("done", version, v0, v1, miss)
        # monitor-flag publish: evaluate the load invariants at the one
        # step that could first violate them (nothing mutates a "done"
        # loader afterwards, and truths only grows, so flag-here is
        # verdict-identical to predicate-at-every-state)
        bad = set()
        if not (v0 == v1 and v0 != _CORRUPT and v0 in s["truths"]):
            bad.add("load_is_committed_consistent")
        if miss:
            bad.add("no_silent_commit_loss")
        if version != v0:
            bad.add("load_version_matches_content")
        if bad:
            s["bad"] = s["bad"] | bad
    actions.append(Action("load_finish", "loader", lfin_guard,
                          lfin_apply, syncs=("registry.load.commit",),
                          pc=(("loader", "fin"),), greads=(),
                          reads=("loader", "mf", "retry_left",
                                 "load_left", "truths", "bad"),
                          writes=("loader", "retry_left", "load_left",
                                  "bad")))

    def lerr_guard(s):
        return s["loader"][0] == "cerr"

    def lerr_apply(s):
        # mid-chain damage: load_checkpoint raises unless base_id moved
        if not _retry(s, s["loader"][1]):
            s["loader"] = ("err",)
    actions.append(Action("load_chain_error", "loader", lerr_guard,
                          lerr_apply,
                          pc=(("loader", "cerr"),), greads=(),
                          reads=("loader", "mf", "retry_left",
                                 "load_left"),
                          writes=("loader", "retry_left",
                                  "load_left")))

    # -- invariants ---------------------------------------------------------
    # monitor-flag style (see the ``bad`` key above): every invariant
    # reads only a flag the violating action itself set, which is what
    # lets the ample rule treat the protocol's pc-stepping actions as
    # invisible. Names are unchanged from PR 11 — every seeded mutation
    # fires exactly the invariant it always fired.
    def inv_consistent(s):
        return "load_is_committed_consistent" not in s["bad"]

    def inv_no_silent_loss(s):
        return "no_silent_commit_loss" not in s["bad"]

    def inv_no_reuse(s):
        return not s["reused"]

    def inv_version(s):
        return "load_version_matches_content" not in s["bad"]

    def inv_trainer_rows(s):
        return not s["t_flag"]

    def is_done(s):
        # a dead trainer with nothing to resume from is an accepted end
        # (the crash-and-never-restart run); everything else quiesces
        # as before
        return s["saver"] == ("idle",) and s["comp"] == ("off",) \
            and s["loader"][0] in ("off", "done", "err")

    return make_model(
        "delta_chain", init, actions,
        [("load_is_committed_consistent", inv_consistent),
         ("no_silent_commit_loss", inv_no_silent_loss),
         ("seqs_never_reused", inv_no_reuse),
         ("load_version_matches_content", inv_version),
         ("trainer_neither_reapplies_nor_skips_rows", inv_trainer_rows)],
        is_done,
        inv_reads=("bad", "reused", "t_flag"),
        monotone_flags=("bad", "reused", "t_flag"),
        # a finished load's observations are published into ``bad`` at
        # load_finish; the "done" tuple payload is never read again
        collapse=(("loader", "done"),),
        notes="delta save -> atomic manifest commit, full-save chain "
              "reset, background compaction, crash/tear budgets, loads "
              "racing everything (checkpoint_delta.py + "
              "checkpoint.load_checkpoint retry) + trainer_restart: "
              "autosave cursor extras, whole-process crash, "
              "fit(resume_from=) cursor-exact resume")


# ---------------------------------------------------------------------------
# Model 5: serving lookup micro-batcher (serving/batcher.py LookupBatcher)
# ---------------------------------------------------------------------------

def serving_batcher(*, snapshot_per_flush: bool = True,
                    drain_on_shutdown: bool = True,
                    requests: int = 3, queue_cap: int = 2,
                    swaps: int = 2) -> Model:
    """The micro-batching lookup scheduler's enqueue/flush/swap/shutdown
    protocol (``serving/batcher.py`` ``LookupBatcher`` vs
    ``registry.apply_delta`` hot-swaps and ``close()``).

    Clients offer ``requests`` lookups into a bounded queue
    (``queue_cap`` — a full or closed queue rejects with a busy
    response, exactly one response either way). The batcher thread runs
    one flush at a time: COLLECT the queued batch, SNAPSHOT the
    published model reference ONCE (the one-reference-grab discipline
    ``ServingModel.lookup`` already pins for single lookups), then
    resolve the batch in two pull sub-steps (the per-variable-group
    pulls of a mixed batch — the window a concurrent hot-swap can land
    in), then respond to every member. A publisher applies deltas
    (``swaps`` budget) at any interleaving, including mid-flush. A
    shutdown stops the queue accepting and DRAINS what was already
    accepted before stopping.

    Invariants:

    * ``batch_serves_one_version`` — every request of one batch is
      answered from the SAME model version: the flush's single
      snapshot. This is the batched-equals-unbatched parity guarantee
      under a delta hot-swap landing mid-batch ("a batch snapshots
      exactly one version").
    * ``no_request_lost_at_shutdown`` — once the batcher is stopped and
      idle with an empty queue, no accepted request is still waiting:
      every enqueued request got exactly one response (rows or busy).

    Mutations: ``snapshot_per_flush=False`` re-reads the live model
    reference at every pull sub-step instead of snapshotting once (the
    seeded ``resnapshot_per_pull`` — a swap between two variable
    groups' pulls hands one batch rows from two versions);
    ``drain_on_shutdown=False`` discards the queue at shutdown without
    responding (the seeded ``drop_queue_on_shutdown`` — accepted
    requests hang forever).

    Bounds: ``requests`` offers, ``queue_cap`` queue slots, ``swaps``
    hot-swaps, one in-flight flush — exhaustive within the budget.
    """
    init: State = {"version": 0, "swaps_left": swaps,
                   "accepting": True, "queue": (),
                   "batcher": ("idle",), "mixed": False}
    for i in range(requests):
        init[f"q{i}"] = "new"          # new|queued|rejected|served
        init[f"q{i}_ver"] = -1

    actions: List[Action] = []

    def offer_ok(i):
        def guard(s):
            return s[f"q{i}"] == "new" and s["accepting"] \
                and len(s["queue"]) < queue_cap

        def apply(s):
            s[f"q{i}"] = "queued"
            s["queue"] = s["queue"] + (i,)
        return Action(f"offer_ok({i})", f"client{i}", guard, apply,
                      syncs=("serving.batch.enqueue",))

    def offer_busy(i):
        def guard(s):
            return s[f"q{i}"] == "new" and \
                (not s["accepting"] or len(s["queue"]) >= queue_cap)

        def apply(s):
            s[f"q{i}"] = "rejected"     # the 429-busy response
        return Action(f"offer_busy({i})", f"client{i}", guard, apply,
                      syncs=("serving.batch.reject",))

    for i in range(requests):
        actions.append(offer_ok(i))
        actions.append(offer_busy(i))

    # -- the flush state machine -------------------------------------------
    def collect_guard(s):
        return s["batcher"] == ("idle",) and s["queue"] != ()

    def collect_apply(s):
        s["batcher"] = ("col", s["queue"])
        s["queue"] = ()
    actions.append(Action("collect", "batcher", collect_guard,
                          collect_apply,
                          syncs=("serving.batch.collect",)))

    def snap_guard(s):
        return s["batcher"][0] == "col"

    def snap_apply(s):
        # the ONE reference grab; the mutation defers reading to the
        # pulls (snapshot value -1 = "no snapshot taken")
        snap = s["version"] if snapshot_per_flush else -1
        s["batcher"] = ("p0", s["batcher"][1], snap)
    actions.append(Action("snapshot", "batcher", snap_guard, snap_apply,
                          syncs=("serving.batch.snapshot",)))

    def serve(s, members, snap):
        ver = snap if snap >= 0 else s["version"]
        for i in members:
            s[f"q{i}"] = "served"
            s[f"q{i}_ver"] = ver

    def pull0_guard(s):
        return s["batcher"][0] == "p0"

    def pull0_apply(s):
        _pc, batch, snap = s["batcher"]
        serve(s, batch[:1], snap)       # first variable group
        s["batcher"] = ("p1", batch, snap)
    actions.append(Action("pull_group_a", "batcher", pull0_guard,
                          pull0_apply, syncs=("serving.batch.pull",)))

    def pull1_guard(s):
        return s["batcher"][0] == "p1"

    def pull1_apply(s):
        _pc, batch, snap = s["batcher"]
        serve(s, batch[1:], snap)       # remaining variable groups
        vers = {s[f"q{i}_ver"] for i in batch}
        if len(vers) > 1:
            s["mixed"] = True
        s["batcher"] = ("resp", batch)
    actions.append(Action("pull_group_b", "batcher", pull1_guard,
                          pull1_apply, syncs=("serving.batch.pull",)))

    def resp_guard(s):
        return s["batcher"][0] == "resp"

    def resp_apply(s):
        s["batcher"] = ("idle",)
    actions.append(Action("respond", "batcher", resp_guard, resp_apply,
                          syncs=("serving.batch.respond",)))

    # -- hot-swap publisher (registry.apply_delta order) --------------------
    def swap_guard(s):
        return s["swaps_left"] > 0

    def swap_apply(s):
        s["swaps_left"] -= 1
        s["version"] += 1
    actions.append(Action("apply_delta", "publisher", swap_guard,
                          swap_apply,
                          syncs=("registry.find", "registry.swap.build",
                                 "registry.swap.commit")))

    # -- shutdown -----------------------------------------------------------
    def stop_guard(s):
        return s["accepting"]

    def stop_apply(s):
        s["accepting"] = False
        if not drain_on_shutdown:
            s["queue"] = ()             # mutated: accepted requests dropped
    actions.append(Action("shutdown", "control", stop_guard, stop_apply,
                          syncs=("serving.batch.shutdown",)))

    # -- invariants ---------------------------------------------------------
    def inv_one_version(s):
        return not s["mixed"]

    def inv_no_lost(s):
        # stopped + idle + empty queue, yet an accepted request still
        # waits: it will never be answered
        if s["accepting"] or s["queue"] != () \
                or s["batcher"] != ("idle",):
            return True
        return all(s[f"q{i}"] != "queued" for i in range(requests))

    def is_done(s):
        return s["batcher"] == ("idle",) and s["queue"] == () \
            and all(s[f"q{i}"] in ("served", "rejected")
                    for i in range(requests))

    return make_model(
        "serving_batcher", init, actions,
        [("batch_serves_one_version", inv_one_version),
         ("no_request_lost_at_shutdown", inv_no_lost)],
        is_done,
        notes="LookupBatcher bounded enqueue -> collect/snapshot/pull/"
              "respond flush vs apply_delta hot-swaps and drain-on-"
              "shutdown (serving/batcher.py)")


# ---------------------------------------------------------------------------
# Model 6: multi-host delta round (per-host shard-local writers + one
# cross-host manifest commit) — ROADMAP item 3, models-first
# ---------------------------------------------------------------------------

def multihost_delta(*, verify_all: bool = True, durable_ack: bool = True,
                    hosts: int = 3, rounds: int = 3) -> Model:
    """Per-host delta writers with a single cross-host manifest commit.

    ``hosts`` interchangeable writer hosts each persist a shard-local
    delta payload for the current round (``ckpt.delta.write``), then
    acknowledge to the coordinator (reserved ``ckpt.multihost.ack`` —
    ack strictly AFTER the durable write). The coordinator verifies it
    holds an ack from EVERY host (reserved ``ckpt.multihost.verify``)
    before the one manifest commit that publishes the cross-host
    version (``ckpt.delta.commit``). A host may crash at any point
    (one-crash budget): a crash before the ack may lose the un-synced
    payload; recovery re-enters the writer loop and re-pushes the
    current round idempotently (``ckpt.writer.run`` — re-writing an
    already-durable payload is a no-op union).

    Invariants (poison-flag form so the commit step stays
    ample-eligible): ``no_torn_cross_host_publish`` — the manifest
    never publishes a version some host's payload is missing for;
    ``committed_version_monotone``.

    Obligation: after every crash/recover detour the fleet still
    converges — ``mf_version`` reaches ``rounds`` on every run.

    Mutations: ``verify_all=False`` commits on a quorum of
    ``hosts - 1`` acks (the "one straggler can't hold the round"
    shortcut) — the missing host's payload is torn out of the
    published version; ``durable_ack=False`` lets a host ack from
    ``idle`` before its payload is durable (ack-before-fsync) — the
    coordinator counts an ack whose bytes never land.
    """
    names = [f"h{i}" for i in range(hosts)]
    init: State = {"round": 1, "mf_version": 0, "acks": frozenset(),
                   "c_pc": "collect", "crash_left": 1,
                   "torn": False, "mono_bad": False}
    for h in names:
        init[f"{h}_pc"] = "idle"
        init[f"{h}_wr"] = frozenset()

    actions: List[Action] = []
    for h in names:
        def wr_apply(s, h=h):
            s[f"{h}_pc"] = "written"
            s[f"{h}_wr"] = s[f"{h}_wr"] | {s["round"]}
        actions.append(Action(
            f"{h}_write", h,
            lambda s, h=h: s[f"{h}_pc"] == "idle"
            and s["c_pc"] == "collect",
            wr_apply, syncs=("ckpt.delta.write",),
            pc=((f"{h}_pc", "idle"), ("c_pc", "collect")),
            greads=(), reads=("round", f"{h}_wr"),
            writes=(f"{h}_pc", f"{h}_wr")))

        def ack_apply(s, h=h):
            s[f"{h}_pc"] = "acked"
            s["acks"] = s["acks"] | {h}
        actions.append(Action(
            f"{h}_ack", h,
            lambda s, h=h: s[f"{h}_pc"] == "written",
            ack_apply, syncs=("ckpt.multihost.ack",),
            pc=((f"{h}_pc", "written"),),
            greads=(), reads=("acks",), writes=(f"{h}_pc", "acks")))
        if not durable_ack:
            # mutated: the ack races the fsync — it can fire while the
            # payload write hasn't happened (and now never will: the
            # host sits in "acked" with nothing on disk)
            actions.append(Action(
                f"{h}_ack_early", h,
                lambda s, h=h: s[f"{h}_pc"] == "idle"
                and s["c_pc"] == "collect",
                ack_apply, syncs=("ckpt.multihost.ack",),
                pc=((f"{h}_pc", "idle"), ("c_pc", "collect")),
                greads=(), reads=("acks",), writes=(f"{h}_pc", "acks")))

        def crash_apply(s, h=h):
            # a crash between the write syscall and the ack may lose
            # the un-synced payload (branch) — once acked, the payload
            # was durable by protocol order, so it survives
            out = dict(s)
            out[f"{h}_pc"] = "dead"
            out["crash_left"] -= 1
            if s[f"{h}_pc"] == "written":
                lost = dict(out)
                lost[f"{h}_wr"] = out[f"{h}_wr"] - {s["round"]}
                return [out, lost]
            return out
        actions.append(Action(
            f"{h}_crash", h,
            lambda s, h=h: s["crash_left"] > 0
            and s[f"{h}_pc"] != "dead",
            crash_apply,
            pc=((f"{h}_pc", "!dead"),), greads=("crash_left",),
            reads=(f"{h}_pc", f"{h}_wr", "round", "crash_left"),
            writes=(f"{h}_pc", f"{h}_wr", "crash_left"),
            dead=lambda s: s["crash_left"] == 0))

        actions.append(Action(
            f"{h}_recover", h,
            lambda s, h=h: s[f"{h}_pc"] == "dead",
            lambda s, h=h: s.__setitem__(f"{h}_pc", "idle"),
            syncs=("ckpt.writer.run",),
            pc=((f"{h}_pc", "dead"),),
            greads=(), reads=(), writes=(f"{h}_pc",)))

    need = hosts if verify_all else hosts - 1

    actions.append(Action(
        "coord_verify", "coordinator",
        lambda s: s["c_pc"] == "collect" and len(s["acks"]) >= need,
        lambda s: s.__setitem__("c_pc", "commit"),
        syncs=("ckpt.multihost.verify",),
        pc=(("c_pc", "collect"),), greads=("acks",),
        reads=(), writes=("c_pc",)))

    def commit_apply(s):
        seq = s["round"]
        if any(seq not in s[f"{h}_wr"] for h in names):
            s["torn"] = True
        if seq <= s["mf_version"]:
            s["mono_bad"] = True
        s["mf_version"] = seq
        s["acks"] = frozenset()
        # the commit ENDS the round for every live host: writes and
        # acks are round-scoped, so a host still mid-write restarts
        # its loop for the new round (otherwise its stale pc would
        # let a round-N ack count toward round N+1)
        for h in names:
            if s[f"{h}_pc"] != "dead":
                s[f"{h}_pc"] = "idle"
        s["round"] = seq + 1
        s["c_pc"] = "collect" if s["round"] <= rounds else "done"
    actions.append(Action(
        "coord_commit", "coordinator",
        lambda s: s["c_pc"] == "commit",
        commit_apply, syncs=("ckpt.delta.commit",),
        pc=(("c_pc", "commit"),), greads=(),
        reads=tuple(["round", "mf_version"]
                    + [f"{h}_wr" for h in names]
                    + [f"{h}_pc" for h in names]),
        writes=tuple(["torn", "mono_bad", "mf_version", "acks",
                      "round", "c_pc"] + [f"{h}_pc" for h in names])))

    return make_model(
        "multihost_delta", init, actions,
        [("no_torn_cross_host_publish", lambda s: not s["torn"]),
         ("committed_version_monotone", lambda s: not s["mono_bad"])],
        lambda s: s["c_pc"] == "done",
        notes="N-host shard-local delta writers, ack-after-durable-"
              "write, verify-all-acks before the single cross-host "
              "manifest commit; crash mid-round recovers by idempotent "
              "re-push (ROADMAP item 3, models-first)",
        inv_reads=("torn", "mono_bad"),
        monotone_flags=("torn", "mono_bad"),
        symmetry=(tuple(names),),
        obligations=(Obligation(
            "fleet_converges_after_idempotent_repush",
            lambda s: s["mf_version"] >= rounds, within=40),))


# ---------------------------------------------------------------------------
# Model 7: elastic training membership (join/leave/failure-detect vs
# barrier-free shard reassignment) — ROADMAP item 3, models-first
# ---------------------------------------------------------------------------

def training_membership(*, fenced_reassign: bool = True,
                        failure_detect: bool = True,
                        workers: int = 2, shards: int = 2,
                        steps: int = 3) -> Model:
    """Worker join/leave/failure-detect against barrier-free resume.

    ``workers`` interchangeable trainer workers own disjoint shard
    sets; worker 0 starts up owning every shard, the rest start out.
    A worker joins by restoring from the committed chain (reserved
    ``train.member.join`` + the real ``trainer.resume.restore``),
    steps on the shards it owns (``trainer.fit.step``), may leave
    gracefully once it owns nothing, and may fail. The failure
    detector (reserved ``train.member.detect``) suspects dead workers
    — and, like any timeout detector, can FALSELY suspect a slow live
    one. The controller grants a suspect's shard to a live worker only
    after fencing: the old owner must be confirmed dead, and the grant
    atomically releases before assigning.

    Invariant: ``shard_never_trained_by_two_live_workers`` — a step
    never writes a shard another live worker also owns (poison flag:
    concurrent optimizer writes on one shard corrupt rows silently).

    Obligation: from every state where some shard has no live owner,
    every run re-establishes a live owner for every shard within the
    bound (detect -> grant -> the grantee is stepping again).

    Mutations: ``fenced_reassign=False`` grants on mere suspicion
    without releasing (the suspect may be alive and still stepping) —
    two live workers train the same shard; ``failure_detect=False``
    drops the detector, so a dead worker's shards are never granted:
    the liveness obligation fires (runs end with an orphaned shard).
    """
    wnames = [f"w{i}" for i in range(workers)]
    snames = tuple(f"s{k}" for k in range(shards))
    init: State = {"suspect": frozenset(), "fail_left": 1,
                   "slow_left": 1, "leave_left": 1,
                   "steps_left": steps, "double": False}
    for w in wnames:
        init[f"{w}_pc"] = "out"
        init[f"{w}_own"] = frozenset()
    init["w0_pc"] = "up"
    init["w0_own"] = frozenset(snames)

    own_keys = tuple(f"{w}_own" for w in wnames)
    pc_keys = tuple(f"{w}_pc" for w in wnames)
    actions: List[Action] = []

    for w in wnames:
        def join_apply(s, w=w):
            s[f"{w}_pc"] = "up"
            s["suspect"] = s["suspect"] - {w}
        actions.append(Action(
            f"{w}_join", w,
            lambda s, w=w: s[f"{w}_pc"] == "out",
            join_apply,
            syncs=("train.member.join", "trainer.resume.restore"),
            pc=((f"{w}_pc", "out"),),
            greads=(), reads=("suspect",),
            writes=(f"{w}_pc", "suspect")))

        def step_apply(s, w=w):
            s["steps_left"] -= 1
            mine = s[f"{w}_own"]
            for o in wnames:
                if o != w and s[f"{o}_pc"] == "up" \
                        and mine & s[f"{o}_own"]:
                    s["double"] = True
        actions.append(Action(
            f"{w}_step", w,
            lambda s, w=w: s[f"{w}_pc"] == "up"
            and s["steps_left"] > 0 and s[f"{w}_own"],
            step_apply, syncs=("trainer.fit.step",),
            pc=((f"{w}_pc", "up"),),
            greads=("steps_left", f"{w}_own"),
            reads=own_keys + pc_keys + ("steps_left",),
            writes=("steps_left", "double"),
            dead=lambda s: s["steps_left"] == 0))

        def fail_apply(s, w=w):
            s[f"{w}_pc"] = "dead"
            s["fail_left"] -= 1
        actions.append(Action(
            f"{w}_fail", w,
            lambda s, w=w: s[f"{w}_pc"] == "up" and s["fail_left"] > 0,
            fail_apply,
            pc=((f"{w}_pc", "up"),), greads=("fail_left",),
            reads=("fail_left",), writes=(f"{w}_pc", "fail_left"),
            dead=lambda s: s["fail_left"] == 0))

        def leave_apply(s, w=w):
            s[f"{w}_pc"] = "out"
            s["leave_left"] -= 1
        actions.append(Action(
            f"{w}_leave", w,
            lambda s, w=w: s[f"{w}_pc"] == "up"
            and not s[f"{w}_own"] and s["leave_left"] > 0,
            leave_apply,
            pc=((f"{w}_pc", "up"),),
            greads=(f"{w}_own", "leave_left"),
            reads=("leave_left",), writes=(f"{w}_pc", "leave_left"),
            dead=lambda s: s["leave_left"] == 0))

        if failure_detect:
            def det_apply(s, w=w):
                s["suspect"] = s["suspect"] | {w}
            actions.append(Action(
                f"detect_dead_{w}", "detector",
                lambda s, w=w: s[f"{w}_pc"] == "dead"
                and w not in s["suspect"],
                det_apply, syncs=("train.member.detect",),
                pc=((f"{w}_pc", "dead"),), greads=("suspect",),
                reads=("suspect",), writes=("suspect",)))
            # the timeout detector's false positive: a live worker
            # suspected for being slow (bounded so the clean model's
            # fencing is what prevents the double-train, not luck)
            # a falsely suspected LIVE worker heartbeats again and
            # clears itself — without this the controller can wedge:
            # a suspected grantee is ineligible for grants forever
            def hb_apply(s, w=w):
                s["suspect"] = s["suspect"] - {w}
            actions.append(Action(
                f"{w}_heartbeat", w,
                lambda s, w=w: s[f"{w}_pc"] == "up"
                and w in s["suspect"],
                hb_apply, syncs=("train.member.detect",),
                pc=((f"{w}_pc", "up"),), greads=("suspect",),
                reads=("suspect",), writes=("suspect",)))

            def det_slow_apply(s, w=w):
                s["suspect"] = s["suspect"] | {w}
                s["slow_left"] -= 1
            actions.append(Action(
                f"detect_slow_{w}", "detector",
                lambda s, w=w: s[f"{w}_pc"] == "up"
                and s["slow_left"] > 0 and w not in s["suspect"],
                det_slow_apply,
                syncs=("train.member.detect",),
                pc=((f"{w}_pc", "up"),),
                greads=("slow_left", "suspect"),
                reads=("suspect", "slow_left"),
                writes=("suspect", "slow_left"),
                dead=lambda s: s["slow_left"] == 0))

    for sk in snames:
        for o in wnames:
            for j in wnames:
                if o == j:
                    continue

                def grant_guard(s, sk=sk, o=o, j=j):
                    if sk not in s[f"{o}_own"] or o not in s["suspect"]:
                        return False
                    if s[f"{j}_pc"] != "up" or j in s["suspect"]:
                        return False
                    if fenced_reassign and s[f"{o}_pc"] != "dead":
                        return False      # the fence: confirmed dead
                    return True

                def grant_apply(s, sk=sk, o=o, j=j):
                    if fenced_reassign:
                        s[f"{o}_own"] = s[f"{o}_own"] - {sk}
                    # mutated: assign WITHOUT release — the suspect
                    # (possibly alive) still owns and steps on it
                    s[f"{j}_own"] = s[f"{j}_own"] | {sk}
                actions.append(Action(
                    f"grant_{sk}_{o}_to_{j}", "controller",
                    grant_guard, grant_apply,
                    pc=((f"{j}_pc", "up"),),
                    greads=(f"{o}_own", "suspect", f"{o}_pc"),
                    reads=(f"{o}_own", f"{j}_own"),
                    writes=(f"{o}_own", f"{j}_own")))

    def covered(s):
        return all(any(sk in s[f"{w}_own"] and s[f"{w}_pc"] == "up"
                       for w in wnames) for sk in snames)

    def inv_single_writer(s):
        return not s["double"]

    def is_done(s):
        owners = [w for sk in snames for w in wnames
                  if sk in s[f"{w}_own"] and s[f"{w}_pc"] == "up"]
        return len(owners) == len(snames) and covered(s)

    return make_model(
        "training_membership", init, actions,
        [("shard_never_trained_by_two_live_workers",
          inv_single_writer)],
        is_done,
        notes="elastic worker join/leave/fail + timeout detector with "
              "false positives; fenced release-then-grant shard "
              "reassignment vs barrier-free resume (ROADMAP item 3, "
              "models-first)",
        inv_reads=("double",), monotone_flags=("double",),
        symmetry=(tuple(wnames),),
        obligations=(Obligation(
            "every_shard_regains_a_live_owner",
            covered, within=24,
            after=lambda s: not covered(s)),))


# ---------------------------------------------------------------------------
# Model 8: N->M reshard through the checkpoint path — ROADMAP item 3,
# models-first
# ---------------------------------------------------------------------------

def reshard(*, apply_before_release: bool = True,
            idempotent_apply: bool = True) -> Model:
    """2 -> 3 host resize migrating embedding rows through the
    checkpoint path.

    Four abstract row ranges: ``r0`` stays on ``h0``; ``r1``
    (h0 -> h2) and ``r3`` (h1 -> h2) migrate to the new host, and
    ``r2`` (h1 -> h0) rebalances between the surviving old hosts —
    three concurrent migrations with two distinct destinations. Per
    row the protocol is copy-then-release: the
    destination persists the row (reserved ``reshard.row.apply``),
    and only then does the source drop its copy and the ownership map
    flip (reserved ``reshard.row.release``). The new host may crash
    once mid-migration: an in-flight (staged, un-released) row
    restarts its migration; the re-apply is idempotent — an
    already-persisted row is recognized and NOT folded a second time.

    Invariants (poison flags): ``no_row_lost`` — at no point is a row
    absent from every host (the release-before-apply crash window);
    ``no_row_double_applied`` — recovery never folds a row into the
    destination twice (double optimizer state corrupts the row).
    End-state: ``resize_publishes_target_ownership`` — once both
    migrations are done the ownership map equals the target exactly.

    Obligation: the resize completes on every run within the bound.

    Mutations: ``apply_before_release=False`` releases the source
    before the destination persisted (a crash in the window leaves
    the row in NO host); ``idempotent_apply=False`` re-folds an
    already-applied row after crash recovery.
    """
    target = ("h0", "h2", "h0", "h2")
    init: State = {"owner": ("h0", "h0", "h1", "h1"),
                   "crash_left": 1, "dup": False, "lost": False,
                   "final_bad": False, "resize": "run"}
    migrations = {"r1": (1, "h0", "h2"), "r3": (3, "h1", "h2"),
                  "r2": (2, "h1", "h0")}
    for m in migrations:
        init[f"{m}_pc"] = "pending"
        init[f"{m}_applied"] = False

    actions: List[Action] = []
    for m, (idx, src, dst) in migrations.items():
        def apply_apply(s, m=m, idx=idx, src=src, dst=dst):
            if s[f"{m}_applied"] and not idempotent_apply:
                s["dup"] = True           # re-folded after recovery
            s[f"{m}_applied"] = True
            if apply_before_release:
                s[f"{m}_pc"] = "staged"
            else:
                # mutated order: this is the SECOND step
                s[f"{m}_pc"] = "done"
        if apply_before_release:
            actions.append(Action(
                f"{m}_apply", dst,
                lambda s, m=m: s[f"{m}_pc"] == "pending",
                apply_apply, syncs=("reshard.row.apply",),
                pc=((f"{m}_pc", "pending"),), greads=(),
                reads=(f"{m}_applied",),
                writes=(f"{m}_pc", f"{m}_applied", "dup")))
        else:
            actions.append(Action(
                f"{m}_apply", dst,
                lambda s, m=m: s[f"{m}_pc"] == "staged",
                apply_apply, syncs=("reshard.row.apply",),
                pc=((f"{m}_pc", "staged"),), greads=(),
                reads=(f"{m}_applied",),
                writes=(f"{m}_pc", f"{m}_applied", "dup")))

        def release_apply(s, m=m, idx=idx, dst=dst):
            ow = list(s["owner"])
            ow[idx] = dst
            s["owner"] = tuple(ow)
            if apply_before_release:
                s[f"{m}_pc"] = "done"
            else:
                s[f"{m}_pc"] = "staged"   # source gone, not yet applied
        if apply_before_release:
            actions.append(Action(
                f"{m}_release", src,
                lambda s, m=m: s[f"{m}_pc"] == "staged",
                release_apply, syncs=("reshard.row.release",),
                pc=((f"{m}_pc", "staged"),), greads=(),
                reads=("owner",), writes=("owner", f"{m}_pc")))
        else:
            actions.append(Action(
                f"{m}_release", src,
                lambda s, m=m: s[f"{m}_pc"] == "pending",
                release_apply, syncs=("reshard.row.release",),
                pc=((f"{m}_pc", "pending"),), greads=(),
                reads=("owner",), writes=("owner", f"{m}_pc")))

    # a destination host crash restarts every migration staged INTO
    # it (its un-released staging area is gone); migrations into the
    # other destination are untouched
    for dst in sorted({d for _i, _s, d in migrations.values()}):
        mine = sorted(m for m, (_i, _s, d) in migrations.items()
                      if d == dst)

        def crash_apply(s, mine=mine):
            s["crash_left"] -= 1
            for m in mine:
                if s[f"{m}_pc"] == "staged":
                    if not s[f"{m}_applied"]:
                        # source already released, destination never
                        # persisted: the row is in NO host
                        s["lost"] = True
                    s[f"{m}_pc"] = "pending"
        actions.append(Action(
            f"{dst}_crash", dst,
            lambda s, mine=mine: s["crash_left"] > 0
            and any(s[f"{m}_pc"] == "staged" for m in mine),
            crash_apply,
            greads=tuple(["crash_left"] + [f"{m}_pc" for m in mine]),
            reads=tuple([f"{m}_pc" for m in mine]
                        + [f"{m}_applied" for m in mine]
                        + ["crash_left"]),
            writes=tuple([f"{m}_pc" for m in mine]
                         + ["lost", "crash_left"]),
            dead=lambda s: s["crash_left"] == 0))

    def finish_apply(s):
        if s["owner"] != target:
            s["final_bad"] = True
        s["resize"] = "done"
    actions.append(Action(
        "resize_finish", "coordinator",
        lambda s: s["resize"] == "run"
        and all(s[f"{m}_pc"] == "done" for m in migrations),
        finish_apply,
        pc=(("resize", "run"),),
        greads=tuple(f"{m}_pc" for m in migrations),
        reads=("owner",), writes=("final_bad", "resize")))

    return make_model(
        "reshard", init, actions,
        [("no_row_lost", lambda s: not s["lost"]),
         ("no_row_double_applied", lambda s: not s["dup"]),
         ("resize_publishes_target_ownership",
          lambda s: not s["final_bad"])],
        lambda s: s["resize"] == "done",
        notes="2->3 host resize: per-row copy-then-release through the "
              "checkpoint path, idempotent re-apply after a crash of "
              "the new host (ROADMAP item 3, models-first)",
        inv_reads=("lost", "dup", "final_bad"),
        monotone_flags=("lost", "dup", "final_bad"),
        obligations=(Obligation(
            "resize_completes",
            lambda s: s["resize"] == "done", within=16),))


# ---------------------------------------------------------------------------
# shipped registry + schedule export
# ---------------------------------------------------------------------------

def shipped_models() -> List[Model]:
    """The eight protocol models the CLI checks exhaustively: five
    shipped-code roles plus the three models-first multi-host designs
    (ROADMAP item 3 — their reserved sync points name the contract the
    implementing PR must emit)."""
    return [delta_chain(), hot_swap(), dirty_tracker(), ha_registry(),
            serving_batcher(), multihost_delta(), training_membership(),
            reshard()]


def sample_traces(model: Model, k: int = 2
                  ) -> List[List[Tuple[str, State]]]:
    """Up to ``k`` representative full traces of a CLEAN model (the
    shortest accepted quiescent run and the deepest state's run) — the
    sampled schedules ``--emit-schedules`` exports for replay."""
    parents: Dict[Any, Tuple[Any, Optional[str]]] = {
        model.init: (None, None)}
    queue = deque([model.init])
    done_states: List[Any] = []
    last = model.init
    while queue:
        fs = queue.popleft()
        last = fs
        state = dict(fs)
        if model.is_done(state) and len(done_states) < 1:
            done_states.append(fs)
        _enabled, succs = _successors(model, state)
        for label, b in succs:
            fb = _freeze(b)
            if fb not in parents:
                parents[fb] = (fs, label)
                queue.append(fb)
    picks = done_states + [last]
    traces = []
    seen = set()
    for fs in picks:
        if fs in seen:
            continue
        seen.add(fs)
        traces.append(_trace_of(parents, fs))
        if len(traces) >= k:
            break
    return traces
