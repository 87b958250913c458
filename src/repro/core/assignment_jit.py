"""Compiled eps-scaling auction LMO: the `lax.while_loop` bidding engine.

The numpy auction in ``repro.core.assignment`` is algorithmically right
for the Frank-Wolfe LMO (it exposes warm-startable dual prices) but
dispatch-bound -- PR 2 modeled its Gauss-Seidel bid chain as ~10us of
numpy dispatch per ~0.5us of arithmetic, which is why scipy's C
Jonker-Volgenant stayed 4-10x faster (that model turned out optimistic;
see "Measured outcome" below). This module compiles the *same*
algorithm into one XLA computation:

* one ``jax.lax.while_loop`` over a fixed-shape ``(n,)``/``(n, n)``
  state -- prices, profits, ``col_of_row``, ``owner``, and the epsilon
  schedule all folded into the carry (no host round-trips, no dynamic
  shapes, traces once per ``n``);
* Jacobi bidding rounds as masked vectorized ops while many rows are
  unassigned (every unassigned row bids simultaneously; contested
  objects resolve by a per-column max);
* the Gauss-Seidel endgame drain as single-bid iterations of the same
  while_loop (an ``O(n)`` row scan with immediate price updates -- the
  serialized eviction chains where Jacobi rounds waste ``O(n^2)`` work);
* an optional forward-reverse variant (``variant="forward_reverse"``)
  that alternates row-bids with column-bids to shorten eviction chains
  on the near-duplicate-row instances label-skew Pi produces;
* ``float64`` throughout via a ``jax.enable_x64(True)`` scope
  around trace and execution (the repo's global x64 default stays off),
  so the 1e-12-relative quantization grid is meaningful.

Exactness and trace equivalence. Identical contract to
``assignment.auction_assignment``: costs are snapped to the shared
1e-12-relative grid, the final epsilon is ``grid / (n + 1)``, and the
per-phase duality-gap certificate (``sum_i slack_i < grid/2``) proves
exact optimality of the quantized problem. All backends therefore
produce the same ``<P, G>`` objective to float-summation noise, and
identical ``learn_topology`` trajectories wherever the quantized
optimum is unique (generic Pi).

Measured outcome (BENCH_stl_fw.json, 2-vCPU CPU container): the
compiled engine beats the numpy auction ~1.8-3.1x steady-state (35 vs
91 ms per warm solve at n=512/budget=64) -- honest but short of the
>= 5x this issue targeted, because once the dispatch tax is gone each
Gauss-Seidel bid is memory-bandwidth-bound, and short of scipy's C
Jonker-Volgenant (~18 ms), which ``lmo="auto"`` therefore still
prefers. The wins that stand: fastest scipy-less backend at scale,
device-resident dual state, and the only LMO formulation that can run
on TPU at all (where the bandwidth-per-bid economics are different --
ROADMAP has the on-hardware follow-up).

Warm start. ``AuctionJitState`` carries the dual prices as a
device-resident f64 array. The Frank-Wolfe contraction
(``state.scaled(1 - gamma)``) is *deferred*: it only multiplies a
python scalar into ``pending_scale``, and the scale is applied inside
the next compiled solve -- so a warm re-solve launches exactly one
device computation and recompiles nothing (the jit cache is keyed on
``n`` and the static config only). On TPU/GPU backends the carried
price buffer is donated back to the solver.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from .assignment import (
    AUCTION_REL_GRID,
    _EPS_SCALING,
    _check_feasible,
    _is_permutation,
    _substitute_forbidden,
)

__all__ = [
    "auction_assignment_jit",
    "AuctionJitState",
    "AUCTION_JIT_GS_THRESHOLD",
    "AUCTION_JIT_JACOBI_THRESHOLD",
]

# Active-bidder count above which bidding runs as bucketed Jacobi rounds
# instead of single-bid Gauss-Seidel iterations. Both sides are compiled,
# so the crossover is a bytes-per-bid ratio, not a dispatch-overhead one
# -- and measured on XLA:CPU the ratio never favors Jacobi (a GS bid and
# a Jacobi bid-slot move the same ~6 O(n) passes, and GS wastes none of
# them on already-assigned slots), so the CPU default is "GS always"
# (threshold n). The Jacobi path is the vectorized formulation an
# accelerator wants; TPU/GPU backends default to 64 pending on-hardware
# measurement (ROADMAP).
AUCTION_JIT_GS_THRESHOLD = None  # resolved per backend, see _default_gs_threshold

# Threshold used whenever the Jacobi stage must actually run: on
# accelerators (vectorized rounds are the point there) and for the
# forward_reverse variant on any backend (reverse rounds live inside the
# Jacobi stage, so a GS-only default would silently disable the variant).
AUCTION_JIT_JACOBI_THRESHOLD = 64


def _default_gs_threshold(n: int) -> int:
    try:
        backend = jax.default_backend()
    except Exception:  # pragma: no cover
        backend = "cpu"
    if backend in ("tpu", "gpu", "cuda", "rocm"):
        return AUCTION_JIT_JACOBI_THRESHOLD
    return n

# Forward-reverse safety valve: reverse (column-bid) rounds provably
# maintain eps-CS but the *mixed* Jacobi alternation has no textbook
# termination proof, so after this many Jacobi rounds within one
# epsilon phase the engine falls back to forward-only rounds (whose
# termination argument -- prices rise by >= eps per award -- is
# unconditional). Chains on near-duplicate-row instances resolve in
# far fewer rounds than this.
_REVERSE_ROUND_CAP = 64

# Default initial epsilon-ladder factor for the compiled engine. The
# numpy solver descends by the classic ~6 per phase; on FW-gradient
# instances that costs ~18 phases whose duality gaps never certify
# early (the 1e-12 grid is ~12 decades below the cost spread). The
# compiled engine starts aggressive and relies on its stagnation rescue
# (see _compiled_core) to relax toward 6 on price-warring instances, so
# the large default trades nothing but rescue retries on hard inputs.
# 3000 measured fastest on warm FW-gradient solves at n=512 (sweep in
# benchmarks/bench_stl_fw.py; 30/100/300/1e3/1e4/3e4 all slower).
_JIT_DEFAULT_SCALING = 3000.0

_NEG_INF = -np.inf
# Same fp floor as the numpy solver: a bid of +eps on a price p only
# registers when eps >~ p * 2^-52; phases below the floor stagnate.
_FP_FLOOR = 2.0 ** -48


@dataclasses.dataclass
class AuctionJitState:
    """Warm-start state threaded between ``auction_assignment_jit`` calls.

    Same role as ``assignment.AuctionState`` (dual prices + certified
    assignment + solve counters), with two differences tuned for the
    compiled engine:

    * ``prices`` is a device-resident float64 ``jax.Array`` -- it never
      leaves the accelerator between Frank-Wolfe iterations.
    * ``scaled(factor)`` is deferred: it folds ``factor`` into
      ``pending_scale`` instead of launching a multiply, and the next
      solve applies the product inside its compiled computation. This
      keeps the FW contraction free and, crucially, avoids touching a
      float64 buffer outside the solver's ``enable_x64`` scope (where
      jnp ops would silently canonicalize it to float32).
    """

    prices: jax.Array | np.ndarray
    col_of_row: np.ndarray
    pending_scale: float = 1.0
    n_phases: int = 0
    n_rounds: int = 0
    n_rebid_rows: int = 0

    def scaled(self, factor: float) -> "AuctionJitState":
        """State with prices scaled by ``factor`` (FW contraction step)."""
        return dataclasses.replace(
            self, pending_scale=self.pending_scale * float(factor)
        )


def _donate_argnums() -> tuple[int, ...]:
    """Donate the warm price buffer on backends where donation is real.

    XLA:CPU ignores donation (and warns about it on every call), so the
    carried buffer is only donated on TPU/GPU -- where re-solving every
    FW iteration would otherwise copy the dual vector each call.
    """
    try:
        backend = jax.default_backend()
    except Exception:  # pragma: no cover - backend probing is best-effort
        backend = "cpu"
    return (2,) if backend in ("tpu", "gpu", "cuda", "rocm") else ()


@functools.lru_cache(maxsize=None)
def _compiled_core(
    n: int,
    forward_reverse: bool,
    validate: bool,
    gs_threshold: int,
    max_iters: int,
):
    """Build (once per config) the jitted fixed-shape auction engine.

    Structure: an outer ``lax.while_loop`` over epsilon phases whose body
    runs two inner while_loops -- masked Jacobi bidding rounds while many
    rows are unassigned, then a chain-following Gauss-Seidel drain -- and
    ends in either a phase check (duality gap -> done, or tighten eps and
    unassign violators) or a stagnation rescue (see below).

    Adaptive epsilon schedule. The classic ladder divides eps by a fixed
    ~6 per phase; on the near-duplicate-row instances the FW gradient
    produces, most of those phases are pure overhead (measured: ~18
    phases, ~1 bid/row/phase, and the duality-gap certificate never
    fires early because the 1e-12 grid sits ~12 decades below the cost
    spread). The compiled engine therefore descends aggressively
    (``scaling`` ~1e3 by default) and *rescues* when a phase stalls: if
    the bid budget is exhausted with rows still unassigned -- the price-
    war pathology fixed-large-scaling auctions hit on heavily tied costs
    -- eps is raised back by the current factor, the factor is relaxed
    toward the classic 6 (sqrt), and the budget grows 4x. Hard instances
    thus converge to textbook behavior while easy ones pay ~5 phases
    instead of ~18. Exactness is untouched: any ladder ending at
    ``eps_final`` with the gap certificate is exact on the quantized
    grid.

    Carry layout (all fixed shapes, f64/i32/bool): prices (n,), profits
    pi (n,) (forward_reverse only), col (n,), owner (n,), eps, eps_run,
    scale s, bid budget, done, counters.
    """
    iota_n = jnp.arange(n, dtype=jnp.int32)
    # Bidding bucket: each Jacobi round serves up to BUCKET bidders, so a
    # round costs O(BUCKET * n) -- gather the active rows, best/second-best
    # by max reductions, O(n) scatter-max conflict resolution -- instead
    # of a masked O(n^2) full-matrix pass (which is what made the first
    # cut of this engine slower than the numpy solver it replaces: the
    # active set shrinks fast, the fixed-shape full pass does not).
    bucket = min(n, 64)
    max_outer = 256  # phases + rescues; the ladder never legitimately needs more

    def best_second(vals):
        """Per-row (max, argmax, second max) using ONLY plain max/min
        reductions. XLA:CPU lowers argmax/top_k to scalar variadic-reduce
        loops (~50-300x slower than a vectorized max at these shapes), so
        the argmax is recovered as min-index-attaining-the-max and the
        second max by masking that single column out."""
        v_best = jnp.max(vals, axis=1)
        j_best = jnp.min(
            jnp.where(vals == v_best[:, None], iota_n[None, :], n), axis=1
        ).astype(jnp.int32)
        v_second = jnp.max(
            jnp.where(iota_n[None, :] == j_best[:, None], _NEG_INF, vals), axis=1
        )
        return v_best, j_best, v_second

    def row_slack(benefit, prices, col):
        """Per-row eps-CS gap; sums to the duality gap (see assignment.py)."""
        maxprof = jnp.max(benefit - prices[None, :], axis=1)
        assigned_val = benefit[iota_n, col] - prices[col]
        return maxprof - assigned_val, maxprof

    def forward_round(benefit, prices, pi, col, owner, n_un, eps_run):
        # up to `bucket` unassigned rows bid simultaneously
        (idx,) = jnp.nonzero(col < 0, size=bucket, fill_value=n)
        idx = idx.astype(jnp.int32)
        valid = idx < n
        vals = benefit[jnp.clip(idx, 0, n - 1)] - prices[None, :]  # (bucket, n)
        v_best, j_best, v_second = best_second(vals)
        bid = jnp.where(valid, v_best + prices[j_best] - v_second + eps_run,
                        _NEG_INF)
        # conflict resolution by scatter-max: highest bid per object wins,
        # ties broken toward the largest row index (deterministic)
        win_price = jnp.full((n,), _NEG_INF).at[j_best].max(bid)
        cand = jnp.where(valid & (bid == win_price[j_best]), idx, -1)
        win_row = jnp.full((n,), -1, jnp.int32).at[j_best].max(cand)
        contested = win_price > _NEG_INF
        # evict current owners of contested objects (they were assigned,
        # hence not bidding, hence disjoint from this round's winners)
        evicted = jnp.where(contested, owner, -1)
        col = col.at[jnp.where(evicted >= 0, evicted, n)].set(-1, mode="drop")
        # install winners
        wr = jnp.where(contested, win_row, n)
        col = col.at[wr].set(iota_n, mode="drop")
        owner = jnp.where(contested, win_row, owner)
        prices = jnp.where(contested, win_price, prices)
        n_un = n_un - jnp.sum(contested) + jnp.sum(evicted >= 0)
        if forward_reverse:
            # winner profits: pi_i = second_best - eps (Bertsekas CS pair)
            won = valid & (win_row[j_best] == idx)
            pi = pi.at[jnp.where(won, idx, n)].set(v_second - eps_run, mode="drop")
        return prices, pi, col, owner, n_un

    def reverse_round(benefit, prices, pi, col, owner, n_un, eps_run):
        """Column-bid round: unowned objects cut price to attract a row.

        For unowned object j: best row i* = argmax_i(benefit[i,j] - pi[i]),
        price drops to (second best) - eps, winner row i* switches to j
        and frees its previous object. Profits rise by >= eps per award,
        the mirror image of the forward round's price rises.
        """
        (jdx,) = jnp.nonzero(owner < 0, size=bucket, fill_value=n)
        jdx = jdx.astype(jnp.int32)
        validc = jdx < n
        rvals = (benefit[:, jnp.clip(jdx, 0, n - 1)] - pi[:, None]).T  # (bucket, n)
        b_best, i_best, b_second = best_second(rvals)
        offer = jnp.where(validc, b_best, _NEG_INF)
        # per-row winner among the columns courting it (highest value;
        # ties toward the largest column index)
        win_val = jnp.full((n,), _NEG_INF).at[i_best].max(offer)
        candc = jnp.where(validc & (offer == win_val[i_best]), jdx, -1)
        win_col = jnp.full((n,), -1, jnp.int32).at[i_best].max(candc)
        row_won = win_val > _NEG_INF
        n_un = n_un - jnp.sum(row_won & (col < 0))
        # price cut for winning columns; never raise an unowned price
        wonc = validc & (win_col[i_best] == jdx)
        p_new = jnp.minimum(prices[jnp.clip(jdx, 0, n - 1)], b_second - eps_run)
        prices = prices.at[jnp.where(wonc, jdx, n)].set(p_new, mode="drop")
        # free the winning rows' previous objects (owned, hence disjoint
        # from the unowned winners being installed)
        freed = jnp.where(row_won, col, -1)
        owner = owner.at[jnp.where(freed >= 0, freed, n)].set(-1, mode="drop")
        wc = jnp.where(row_won, win_col, n)
        owner = owner.at[wc].set(iota_n, mode="drop")
        col = jnp.where(row_won, win_col, col)
        # winner profits follow the awarded pair: pi_i = benefit[i, j] - p_j
        wcc = jnp.clip(wc, 0, n - 1)
        pi = jnp.where(row_won, benefit[iota_n, wcc] - prices[wcc], pi)
        return prices, pi, col, owner, n_un

    def jacobi_stage(benefit, prices, pi, col, owner, n_un, eps_run, eps_final,
                     rounds, budget):
        """Inner loop 1: masked Jacobi rounds while many rows are unassigned."""

        def cond(c):
            prices, pi, col, owner, n_un, rounds, bids, phase_rounds = c
            return (n_un > gs_threshold) & (bids < budget) & (rounds < max_iters)

        def body(c):
            prices, pi, col, owner, n_un, rounds, bids, phase_rounds = c
            prices, pi, col, owner, n_un = forward_round(
                benefit, prices, pi, col, owner, n_un, eps_run
            )
            if forward_reverse:
                # reverse rounds only before the final-eps phase and only
                # while under the safety cap (see _REVERSE_ROUND_CAP)
                use_rev = (eps_run > eps_final) & (phase_rounds < _REVERSE_ROUND_CAP)
                prices, pi, col, owner, n_un = jax.lax.cond(
                    use_rev,
                    lambda args: reverse_round(benefit, *args, eps_run),
                    lambda args: args,
                    (prices, pi, col, owner, n_un),
                )
            # budget accounting: a round serves up to `bucket` bidders
            return (prices, pi, col, owner, n_un, rounds + 1,
                    bids + jnp.asarray(float(bucket), jnp.float64),
                    phase_rounds + 1)

        c = (prices, pi, col, owner, n_un, rounds,
             jnp.asarray(0.0, jnp.float64), jnp.asarray(0, jnp.int32))
        prices, pi, col, owner, n_un, rounds, bids, _ = jax.lax.while_loop(
            cond, body, c
        )
        return prices, pi, col, owner, n_un, rounds, bids

    def gs_stage(benefit, prices, col, owner, n_un, eps_run, rounds, bids, budget):
        """Inner loop 2: chain-following Gauss-Seidel drain.

        One bid per iteration with immediate price update; the evicted
        row (if any) bids next -- the same LIFO chain order as the numpy
        solver's stack, which matters on the long eviction chains that
        near-duplicate-row instances produce. Falls back to the smallest
        unassigned index when a chain terminates.
        """

        def cond(c):
            prices, col, owner, n_un, rounds, bids, last = c
            return (n_un > 0) & (bids < budget) & (rounds < max_iters)

        def body(c):
            prices, col, owner, n_un, rounds, bids, last = c
            i = jnp.where(
                last >= 0,
                last,
                jnp.min(jnp.where(col < 0, iota_n, n)),
            ).astype(jnp.int32)
            # same max/min-reduce argmax trick as best_second above
            row = benefit[jnp.clip(i, 0, n - 1)] - prices
            v_best = jnp.max(row)
            j = jnp.min(jnp.where(row == v_best, iota_n, n)).astype(jnp.int32)
            v_second = jnp.max(jnp.where(iota_n == j, _NEG_INF, row))
            prices = prices.at[j].add(v_best - v_second + eps_run)
            old = owner[j]
            col = col.at[jnp.where(old >= 0, old, n)].set(-1, mode="drop")
            col = col.at[i].set(j)
            owner = owner.at[j].set(i)
            n_un = n_un - 1 + (old >= 0)
            return (prices, col, owner, n_un, rounds + 1, bids + 1.0, old)

        c = (prices, col, owner, n_un, rounds, bids, jnp.asarray(-1, jnp.int32))
        prices, col, owner, n_un, rounds, bids, _ = jax.lax.while_loop(
            cond, body, c
        )
        return prices, col, owner, n_un, rounds, bids

    def core(cost, rel_grid, warm_prices, warm_scale, warm_col, have_warm, s0):
        # --- fused prepare: validation + forbidden sentinel + grid snap ---
        # (one device dispatch per solve; the equivalent host numpy sweeps
        # dominated warm-solve time at n >= 512)
        if validate:
            bad = jnp.isnan(cost).any() | jnp.isneginf(cost).any()
            forbidden = jnp.isposinf(cost)
            n_forb = jnp.sum(forbidden)
            blocked = forbidden.all(axis=1).any() | forbidden.all(axis=0).any()
            hi = jnp.max(jnp.where(forbidden, _NEG_INF, cost))
            lo = jnp.min(jnp.where(forbidden, jnp.inf, cost))
            sentinel = hi + n * (hi - lo) + jnp.maximum(jnp.abs(hi), 1.0)
            filled = jnp.where(forbidden, sentinel, cost)
            # same grid formula as assignment._quantize, scale from the
            # finite entries only (the sentinel would coarsen it ~(n+1)x)
            scale = jnp.max(jnp.abs(jnp.where(forbidden, 0.0, cost)))
        else:
            # LMO fast path: the FW gradient is finite by construction
            bad = jnp.asarray(False)
            forbidden = jnp.zeros((0, 0), bool)
            n_forb = jnp.asarray(0, jnp.int32)
            blocked = jnp.asarray(False)
            filled = cost
            scale = jnp.max(jnp.abs(cost))
        grid = scale * rel_grid
        quantized = jnp.where(grid > 0.0, jnp.round(filled / grid) * grid, filled)
        benefit = -quantized
        spread = jnp.max(benefit) - jnp.min(benefit)
        tied = spread <= 0.0
        eps_final = jnp.maximum(grid, np.finfo(np.float64).tiny) / (n + 1)
        gap_tol = 0.5 * grid

        # --- warm-start validity (host already vetted shape+permutation;
        # the price-spread guard mirrors the numpy solver) ---
        wp = warm_prices * warm_scale
        warm_ok = (
            have_warm
            & jnp.isfinite(wp).all()
            & ((jnp.max(wp) - jnp.min(wp)) <= 8.0 * spread)
        )
        prices = jnp.where(warm_ok, wp, 0.0)
        col = jnp.where(warm_ok, warm_col, -1)
        eps0 = jnp.where(
            warm_ok,
            jnp.asarray(np.inf, jnp.float64),  # "first warm check" flag
            jnp.maximum(spread / s0, eps_final),
        )
        if forward_reverse:
            pi = jnp.max(benefit - prices[None, :], axis=1)
        else:
            pi = jnp.zeros((n,))  # profits only drive reverse rounds
        owner = jnp.full((n,), -1, jnp.int32)
        owner = owner.at[jnp.where(col >= 0, col, n)].set(iota_n, mode="drop")

        price_mag0 = jnp.max(jnp.abs(prices))
        eps_run0 = jnp.maximum(eps0, price_mag0 * _FP_FLOOR)

        carry0 = dict(
            prices=prices,
            pi=pi,
            col=col,
            owner=owner,
            n_un=jnp.sum(col < 0),
            eps=eps0,
            eps_run=jnp.where(jnp.isinf(eps0), eps0, eps_run0),
            s=s0,
            budget=jnp.asarray(8.0 * n + 2048.0, jnp.float64),
            done=tied | bad | blocked,  # skip the loop on degenerate input
            phases=jnp.asarray(0, jnp.int32),
            rounds=jnp.asarray(0, jnp.int32),
            rebid=jnp.asarray(n, jnp.int32),
            outer=jnp.asarray(0, jnp.int32),
        )

        def cond(c):
            return (~c["done"]) & (c["outer"] < max_outer) & (c["rounds"] < max_iters)

        def rescue(c, stash):
            """Phase stalled (budget out, rows unassigned): the price-war
            pathology of an over-aggressive eps descent. Raise eps back by
            the current factor, relax the factor toward the classic 6, and
            let the next outer iteration retry with a 4x budget."""
            eps_new = jnp.minimum(c["eps"] * c["s"], spread / float(_EPS_SCALING))
            s_new = jnp.maximum(jnp.sqrt(c["s"]), float(_EPS_SCALING))
            price_mag = jnp.max(jnp.abs(c["prices"]))
            return {
                **c,
                "eps": eps_new,
                "eps_run": jnp.maximum(eps_new, price_mag * _FP_FLOOR),
                "s": s_new,
                "budget": c["budget"] * 4.0,
            }

        def phase_check(c, stash):
            slack, maxprof = row_slack(benefit, c["prices"], c["col"])
            gap = jnp.sum(slack)
            first_warm = jnp.isinf(c["eps"])
            cert = gap_tol > 0.0
            done = jnp.where(
                first_warm,
                cert & (gap <= gap_tol),
                (cert & (gap <= gap_tol))
                | (c["eps_run"] <= eps_final)
                # fp floor already active: tightening eps cannot change
                # any bid; accept the eps_run-optimal assignment
                | (c["eps_run"] > c["eps"]),
            )
            # n_rebid_rows bookkeeping mirrors the numpy solver: the count
            # of eps-CS-violating rows at the warm check, 0 on the
            # zero-bidding fast path
            rebid = jnp.where(
                first_warm,
                jnp.where(done, 0, jnp.sum(slack > eps_final)).astype(jnp.int32),
                c["rebid"],
            )
            eps_new = jnp.where(
                first_warm,
                jnp.maximum(jnp.minimum(jnp.max(slack), spread) / c["s"], eps_final),
                jnp.maximum(c["eps"] / c["s"], eps_final),
            )
            price_mag = jnp.max(jnp.abs(c["prices"]))
            eps_run_new = jnp.maximum(eps_new, price_mag * _FP_FLOOR)
            # unassign the rows whose eps-CS the next phase must repair
            drop = (~done) & (slack > eps_new)
            col = jnp.where(drop, -1, c["col"])
            owner = jnp.full((n,), -1, jnp.int32)
            owner = owner.at[jnp.where(col >= 0, col, n)].set(iota_n, mode="drop")
            # re-sync profits to the implicit duals (exact CS, eps = 0)
            return {
                **c,
                "pi": maxprof,
                "col": col,
                "owner": owner,
                "n_un": jnp.sum(drop),
                "eps": eps_new,
                "eps_run": eps_run_new,
                "done": done,
                "phases": c["phases"] + jnp.where(done, 0, 1).astype(jnp.int32),
                "rebid": rebid,
            }

        def body(c):
            prices, pi, col, owner, n_un, rounds, bids = jacobi_stage(
                benefit, c["prices"], c["pi"], c["col"], c["owner"], c["n_un"],
                c["eps_run"], eps_final, c["rounds"], c["budget"],
            )
            prices, col, owner, n_un, rounds, bids = gs_stage(
                benefit, prices, col, owner, n_un, c["eps_run"], rounds, bids,
                c["budget"],
            )
            c = {
                **c,
                "prices": prices,
                "pi": pi,
                "col": col,
                "owner": owner,
                "n_un": n_un,
                "rounds": rounds,
            }
            c = jax.lax.cond(n_un > 0, rescue, phase_check, c, None)
            return {**c, "outer": c["outer"] + 1}

        out = jax.lax.while_loop(cond, body, carry0)
        # fully tied input: any permutation is optimal -- keep a valid
        # warm one, else identity; prices reset (numpy solver contract)
        tied_col = jnp.where(have_warm, warm_col, iota_n)
        col_out = jnp.where(tied, tied_col, out["col"])
        prices_out = jnp.where(tied, 0.0, out["prices"])
        rebid_out = jnp.where(warm_ok, out["rebid"], n).astype(jnp.int32)
        flags = jnp.stack([
            bad.astype(jnp.float64),
            blocked.astype(jnp.float64),
            n_forb.astype(jnp.float64),
            tied.astype(jnp.float64),
            (out["done"] | tied).astype(jnp.float64),
        ])
        return (
            col_out,
            prices_out,
            out["phases"],
            out["rounds"],
            rebid_out,
            flags,
            forbidden,
        )

    return jax.jit(core, donate_argnums=_donate_argnums())


def auction_assignment_jit(
    cost: np.ndarray,
    warm: AuctionJitState | None = None,
    *,
    rel_grid: float = AUCTION_REL_GRID,
    scaling: float | None = None,
    variant: str = "forward",
    gs_threshold: int | None = AUCTION_JIT_GS_THRESHOLD,
    max_iters: int | None = None,
    validate: bool = True,
) -> tuple[np.ndarray, AuctionJitState]:
    """Compiled forward(-reverse) auction with adaptive epsilon scaling.

    Drop-in analogue of ``assignment.auction_assignment`` running as a
    single jitted ``lax.while_loop`` (see module docstring). The host
    wrapper keeps the exact input contract of the numpy solver --
    square-matrix validation, ``+inf`` forbidden pairs via a finite
    sentinel, NaN/-inf rejection, the shared 1e-12-relative
    quantization, and the n == 0 / n == 1 / all-tied shortcuts -- then
    hands the fixed-shape bidding war to the compiled engine.

    Args:
      cost: (n, n) cost matrix; ``+inf`` marks forbidden pairs.
      warm: ``AuctionJitState`` from a previous solve on a nearby cost
        matrix (pass ``state.scaled(1 - gamma)`` across FW steps; the
        contraction is applied inside the compiled solve).
      rel_grid: quantization grid relative to ``max|cost|`` (exactness
        certificate; must match the caller's canonicalization).
      scaling: initial epsilon-ladder factor between phases. Default
        ``None`` = the aggressive ``_JIT_DEFAULT_SCALING`` (3000): the
        engine's stagnation rescue relaxes it toward the classic 6 on
        instances that price-war (see ``_compiled_core``), so the big
        default is safe -- it just skips the ~13 ladder phases that
        measured as pure overhead on FW-gradient instances.
      variant: ``"forward"`` (row bids only, default) or
        ``"forward_reverse"`` (alternating row- and column-bids;
        shortens eviction chains on some near-duplicate-row instances
        -- benchmark before preferring it, see BENCH_stl_fw.json).
      gs_threshold: active-bidder count below which the engine switches
        from Jacobi rounds to single-bid Gauss-Seidel iterations.
        Default ``None`` resolves per backend: ``n`` (GS always) on CPU
        where the bucketed Jacobi round never wins the bytes-per-bid
        race, 64 on TPU/GPU where the vectorized rounds are the point
        -- except under ``variant="forward_reverse"``, which always
        defaults to 64 (reverse rounds run inside the Jacobi stage, so
        a GS-only threshold would silently disable the variant).
      max_iters: safety valve on total bidding rounds; default
        ``500 * n + 200_000``.
      validate: compile the NaN/-inf rejection and ``+inf``
        forbidden-pair machinery into the solve (default). Callers whose
        matrices are finite by construction (the FW LMO) pass ``False``
        to drop those O(n^2) scans from the per-solve dispatch.

    Returns:
      ``(col_of_row, state)`` -- the assignment (host int64 array) and
      the device-resident dual state for the next warm call.
    """
    cost = np.asarray(cost, dtype=np.float64)
    if cost.ndim != 2 or cost.shape[0] != cost.shape[1]:
        raise ValueError(
            f"auction_assignment_jit expects a square cost matrix, got {cost.shape}"
        )
    if variant not in ("forward", "forward_reverse"):
        raise ValueError(f"unknown auction variant {variant!r}")
    if scaling is None:
        scaling = _JIT_DEFAULT_SCALING
    scaling = float(scaling)
    if scaling <= 1.0:
        raise ValueError(f"scaling must exceed 1, got {scaling}")
    n = cost.shape[0]
    if gs_threshold is None:
        # reverse rounds only run inside the Jacobi stage, so the CPU
        # default of "GS always" would make forward_reverse a silent
        # no-op -- requesting the variant implies wanting the rounds
        gs_threshold = (
            AUCTION_JIT_JACOBI_THRESHOLD
            if variant == "forward_reverse"
            else _default_gs_threshold(n)
        )
    if n == 0:
        return (
            np.empty(0, dtype=np.int64),
            AuctionJitState(np.empty(0), np.empty(0, np.int64)),
        )
    if n == 1:
        _, forbidden = _substitute_forbidden(cost)
        col = np.zeros(1, dtype=np.int64)
        _check_feasible(forbidden, col)
        return col, AuctionJitState(prices=np.zeros(1), col_of_row=col)
    if max_iters is None:
        max_iters = 500 * n + 200_000

    # host-side warm vetting is O(n) (shape + permutation on the
    # host-resident col_of_row; prices are checked by .shape only -- a
    # device array must NOT be pulled to the host here, that would add a
    # blocking D2H sync per FW iteration); everything O(n^2) --
    # validation, quantization, the finiteness/spread guards on the
    # carried prices -- runs fused inside the single compiled dispatch
    have_warm = (
        warm is not None
        and getattr(warm.prices, "shape", None) == (n,)
        and np.isfinite(warm.pending_scale)
        and _is_permutation(np.asarray(warm.col_of_row), n)
    )
    core = _compiled_core(
        n, variant == "forward_reverse", validate, int(gs_threshold),
        int(max_iters),
    )
    with jax.enable_x64(True):
        if have_warm:
            warm_prices = jnp.asarray(warm.prices, jnp.float64)
            warm_scale = jnp.asarray(warm.pending_scale, jnp.float64)
            warm_col = jnp.asarray(warm.col_of_row, jnp.int32)
        else:
            warm_prices = jnp.zeros((n,), jnp.float64)
            warm_scale = jnp.asarray(1.0, jnp.float64)
            warm_col = jnp.full((n,), -1, jnp.int32)
        col_j, prices_j, phases, rounds, rebid, flags, forbidden_j = core(
            jnp.asarray(cost, jnp.float64),
            jnp.asarray(rel_grid, jnp.float64),
            warm_prices,
            warm_scale,
            warm_col,
            jnp.asarray(have_warm),
            jnp.asarray(scaling, jnp.float64),
        )
        col = np.asarray(col_j, dtype=np.int64)  # one sync point
        fl = np.asarray(flags)
    if fl[0] != 0.0:
        raise ValueError("cost matrix may not contain NaN or -inf")
    if fl[1] != 0.0:
        raise ValueError("no feasible assignment: a row/column is fully forbidden")
    if fl[4] == 0.0:
        raise RuntimeError(
            f"auction_jit did not converge in {max_iters} bidding rounds "
            f"(n={n}); cost matrix may be adversarial"
        )
    forbidden = np.asarray(forbidden_j) if validate and fl[2] != 0.0 else None
    _check_feasible(forbidden, col)
    if fl[3] != 0.0:  # fully tied input: numpy-solver contract, zero prices
        return col, AuctionJitState(prices=np.zeros(n), col_of_row=col.copy())
    state = AuctionJitState(
        prices=prices_j,
        col_of_row=col.copy(),
        n_phases=int(phases),
        n_rounds=int(rounds),
        n_rebid_rows=int(rebid),
    )
    return col, state
