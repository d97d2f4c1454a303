"""The serving engine: paged cache + continuous batcher + the model.

One :class:`ServingEngine` is one replica: it owns a paged K/V pool, a
:class:`~flextree_tpu.serving.batcher.ContinuousBatcher`, and three jitted
programs — prefill (one compile per distinct prompt length), the paged
decode step (ONE compile for the server lifetime; slot count, table
width, and pool shape are all static) and the greedy pick over the
logits either leaves on the device (:func:`greedy_ids`).  The decode
step runs **fused** paged attention by default (``fused=True`` →
``ops.paged_attention`` streams K/V blocks through an online softmax,
never materializing the gathered row; within a pinned tolerance of the
gather oracle);
``fused=False`` keeps the gather path, which is the one proven bitwise
against ``generate``.  ``step()`` is one scheduling round:

1. **resume** — preempted sequences re-enter free slots with strict
   priority (swap-in scatter of their saved K/V, or prefill-replay
   recompute), continuing bit-identically where they stopped;
2. **admit** — pop queued requests into free slots under the block
   (reservation or on-demand, per ``BatcherConfig.admission``) and
   prefill-token budgets; each admitted request runs prefill, scatters
   its K/V into its blocks, and emits its first token (that's the TTFT
   moment — continuous batching's whole advantage is that this happens
   while other sequences keep decoding);
3. **grow** — on-demand admission allocates each active sequence's next
   decode block as its length crosses a block boundary; pool exhaustion
   preempts the newest resident sequence (swap-out/recompute) until the
   survivors fit;
4. **decode** — one paged decode step over all S slots; active rows
   advance one token, empty rows are masked no-ops;
5. **retire** — finished sequences (stop token or ``max_new_tokens``)
   free their blocks immediately and land in ``completed``.

**Dispatch ahead.**  A step may dispatch the NEXT round before it fetches
its own round's ids, when the host already knows that round
(``ContinuousBatcher.continues``): the same slots, none with a stop
token, nothing waiting to resume or to be admitted, and each slot going
on by its round's kind (one token a slot: it picks greedily, does not
finish with this round's token, and writes one position further in a
block it holds; a diffusion pass: this pass does not deliver its last
block, and it holds the blocks the next pass writes).  The next round
takes what it needs of this one as it lies on the device: the picked ids,
or the pass's reveal folded into the open blocks by its pick.  The step
then fetches, records and retires while the device runs the next round,
and the next step finds its round already dispatched.  Whatever reads or
moves a slot outside a round delivers the round in flight first
(:meth:`ServingEngine.settle`); one that is dropped with its engine (a
replica's drain) delivers nothing.

Sampling is per request, and token ids, not logits, cross to the host:
the greedy pick is :func:`greedy_ids` on the device (``generate``'s
``jnp.argmax`` on identical logits — the bench's floor), one ``(S,)``
int32 fetch a decode round and one id a prefill.  Only a request with
``temperature > 0`` has its own logits row fetched, for ``sample_token``
under the same presplit key schedule ``generate`` uses, so a sampled
request through the engine reproduces ``generate(..., key=PRNGKey(seed))``
exactly.

For a model that generates by diffusion over blocks (``Block.block_len``
B > 1) step 4 is one PASS of the open blocks over every slot, each at its
own phase (a denoising pass, or the commit pass that keeps a clean block's
K/V), and the reveal on the device (:func:`unmask_greedy_ids`); a slot
delivers a whole block of tokens at one stamp or none, and ``step()``
reports ``emitted``, the tokens delivered (``decoded`` for the other
blocks).  What differs between a round and a pass is the round's kind
(``_Tokens``, ``_Blocks``), chosen once when the engine is built: its
host arrays and its half of ``continues`` (the batcher's), its programs
and what it records.  One record (``_Round``), one dispatch and one
delivery serve both.

Timestamps come from the module-level ``_now`` (monotonic), injectable
for tests the same way ``runtime.supervisor._wall`` is.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..models.configs import (
    block_of,
    config_from_dict,
    init_model_params,
    position_parts,
    slot_parts,
)
from ..models.generate import prefill, prefill_suffix, sample_token
from ..models.moe import MOE_COUNTS
from ..models.transformer import TransformerConfig
from ..obs import MetricsRegistry, current_recorder, record_event, span
from .batcher import (
    BatcherConfig,
    BlockDiffusion,
    ContinuousBatcher,
    OneToken,
    Request,
    SeqState,
)
from .costs import cache_bytes_per_position, state_bytes_per_slot
from .kv_cache import (
    CacheExhausted,
    PagedCacheConfig,
    decode_attention_layers,
    export_blocks,
    gather_seq,
    init_pools,
    init_state,
    make_paged_decode_fn,
    read_state,
    write_imported,
    write_prefill,
    write_prefill_at,
    write_state,
    write_swapped,
)
from .migration import MigrationError, pack_kv, unpack_kv, unpack_state

# cache-occupancy histogram buckets: fractions of the allocatable pool in
# use, observed once per scheduling round (engine.report() embeds it)
_OCCUPANCY_BUCKETS = tuple(round(0.1 * i, 1) for i in range(1, 11))

# migration payload size buckets (bytes on the wire, power-of-4-ish):
# tiny bench models ship KB, production shapes ship MB — one histogram
# covers both
_MIGRATION_BYTES_BUCKETS = (
    1 << 10, 1 << 12, 1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22, 1 << 24,
)

__all__ = ["CompletedRequest", "ServingEngine"]

# injection point for tests (patch this, not time.monotonic) — one clock
# for arrival stamps (load generator) and token stamps (engine)
_now = time.monotonic


def greedy_ids(logits):
    """(N, V) logits -> (N,) int32 ids: ``generate``'s own greedy pick
    (``sample_token`` at temperature 0), the first index of each row's
    maximum as ``np.argmax`` takes it.  A named ``def`` so that its
    program shows in a profile as ``jit_greedy_ids``, apart from the
    decode program the benchmark finds by name."""
    return sample_token(logits)


def unmask_greedy_ids(logits, masked, reveal, tokens=None, commit=None,
                      mask_id=0):
    """A diffusion pass's pick: (S, B, V) logits, (S, B) bool ``masked``
    places, (S,) int32 ``reveal`` counts -> ``(ids, revealed)``: (S, B)
    int32 the greedy token at every place (``greedy_ids``'s first index of
    the maximum), and (S, B) bool the ``reveal`` masked places of each
    slot whose token is the most probable, by its softmax probability in
    float32 over the whole vocabulary, ties to the lower place.  Only the
    ids and the mask cross to the host, never a logits row.  A named
    ``def`` (``jit_unmask_greedy_ids`` in a profile: the pick program the
    benchmark finds by ``greedy_ids``), scope ``ft_unmask``.

    Given the open blocks' ``tokens`` (S, B) and ``commit`` (S,) bool, the
    slots whose pass is a commit pass, it also folds the reveal into the
    next pass's open blocks and returns ``(ids, revealed, tokens,
    masked)``: the revealed ids put in place and their places unmasked, or
    after a commit pass a fresh block, ``mask_id`` at every place and all
    of them masked.  That is what ``record_block_pass`` leaves on the
    host, and it never crosses."""
    with jax.named_scope("ft_unmask"):
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        conf = 1.0 / jnp.sum(jnp.exp(logits - top), axis=-1)
        conf = jnp.where(masked, conf, -1.0)
        place = jnp.arange(masked.shape[1])
        # place j is ahead of place i: more probable, or as probable and
        # lower; a place's rank is how many are ahead of it
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None])
            & (place[None, None, :] < place[None, :, None])
        )
        rank = ahead.sum(axis=-1)
        revealed = masked & (rank < reveal[:, None])
        if tokens is None:
            return ids, revealed
        fresh = commit[:, None]
        return (
            ids, revealed,
            jnp.where(fresh, mask_id, jnp.where(revealed, ids, tokens)),
            fresh | (masked & ~revealed),
        )


class _RevealView:
    """``(logits, masked, reveal) -> (ids, revealed)``: the pick program
    a diffusion pass runs, with no block to fold (no slot commits, tokens
    of 0), and its first two results; ``lower`` lowers that program.  How
    a caller outside the rounds takes the reveal alone, on the executable
    the rounds run."""

    def __init__(self, pick, mask_id: int):
        self.pick, self.mask_id = pick, mask_id
        self.__name__ = pick.__name__

    def _args(self, logits, masked, reveal) -> tuple:
        s, b = np.shape(masked)
        return (logits, masked, reveal, np.zeros((s, b), np.int32),
                np.zeros((s,), bool))

    def __call__(self, logits, masked, reveal) -> tuple:
        args = self._args(logits, masked, reveal)
        return self.pick(*args, mask_id=self.mask_id)[:2]

    def lower(self, logits, masked, reveal):
        args = self._args(logits, masked, reveal)
        return self.pick.lower(*args, mask_id=self.mask_id)


@dataclasses.dataclass
class _Round:
    """A round (a diffusion pass) from its dispatch to its delivery: its id
    (the step that owns it) and the slots it runs; on the device until it
    is delivered, what the host fetches (``fetch``: the ids, and a pass's
    revealed places; ``counts``: the routers', where the block routes) and
    what the round after it takes there (``feed``: the ids, or a pass's
    open blocks with its reveal folded in); ``logits`` only where
    ``sampled`` slots sample on the host.  Once delivered, what the routers
    counted (``moe``), the kind's counts (``blocks``: a pass's revealed,
    committed, emitted) and the tokens it delivered (``emitted``)."""

    rid: int
    active: list
    fetch: tuple
    counts: object
    feed: tuple
    sampled: int
    logits: object
    moe: dict = dataclasses.field(default_factory=dict)
    blocks: dict = dataclasses.field(default_factory=dict)
    emitted: int = 0
    delivered: bool = False


class _Tokens(OneToken):
    """A round of one token a slot as the engine runs it: the decode
    program (over the state too, where the block holds one), then
    :func:`greedy_ids`; the round after it takes those ids, on the
    device."""

    def span_args(self, eng) -> dict:
        return {}

    def dispatch(self, eng, arrays, feed) -> tuple:
        """Dispatch the round's programs on ``arrays`` (``feed``: what the
        round in flight hands it, where it goes ahead of one).  Returns
        ``(logits, what the host fetches, the routers' counts or None, what
        the round after it takes)``."""
        tables, lengths, tokens, _ = arrays
        # a model with routed experts hands out a third result, what its
        # routers did this round
        logits, *routed = eng._decode_round(
            tables, lengths, tokens if feed is None else feed[0]
        )
        ids = eng._greedy_ids(logits)
        return logits, (ids,), routed[0]["counts"] if routed else None, (ids,)

    def record(self, eng, rnd, fetched, now) -> tuple:
        """Record each slot's token: the device's pick, or one sampled on
        the host from the slot's logits row.  Returns ``(tokens delivered,
        the kind's counts)``."""
        (ids,) = fetched
        for slot in rnd.active:
            tok = eng._pick(slot, ids, rnd.logits, slot)
            eng.batcher.record_decode_token(slot, tok, now)
        return len(rnd.active), {}


class _Blocks(BlockDiffusion):
    """A diffusion pass as the engine runs it: the pass over every slot's
    open block, each at its own phase (a denoising pass, or the commit pass
    of a block whose last place the pass before revealed), then
    :func:`unmask_greedy_ids`, which folds the reveal into the next pass's
    open blocks on the device: what the pass after it takes."""

    def span_args(self, eng) -> dict:
        return dict(block_len=self.length,
                    query_rows=eng.bcfg.slots * self.length,
                    denoise_steps=self.steps)

    def dispatch(self, eng, arrays, feed) -> tuple:
        """As :meth:`_Tokens.dispatch`; the host fetches the ids and the
        revealed places."""
        tables, lengths, tokens, masked, reveal, commit, act = arrays
        if feed is not None:
            tokens, masked = feed
        logits, eng.pools, routed = eng._decode(
            eng.params, eng.pools, tables, lengths, tokens, act
        )
        ids, revealed, *folded = eng._pass_pick(
            logits, masked, reveal, tokens, commit, mask_id=self.mask_id
        )
        return logits, (ids, revealed), routed["counts"], tuple(folded)

    def record(self, eng, rnd, fetched, now) -> tuple:
        """Record what the pass did (``record_block_pass``), and count it.
        Returns ``(tokens delivered, the pass's counts: revealed, committed,
        emitted)``."""
        ids, revealed = fetched
        done = eng.batcher.record_block_pass(rnd.active, ids, revealed, now)
        m = eng.metrics
        for slot in done["first"]:
            m.histogram("serve.ttft_ms").observe(
                (now - eng.batcher.slots[slot].request.arrival_s) * 1e3
            )
        m.counter("serve.tokens_revealed").inc(done["revealed"])
        m.counter("serve.commit_passes").inc(done["committed"])
        m.counter("serve.blocks_committed").inc(done["blocks"])
        return done["emitted"], {
            k: done[k] for k in ("revealed", "committed", "emitted")
        }


@dataclasses.dataclass(frozen=True)
class CompletedRequest:
    """A finished request's tokens and latency-relevant timestamps (all
    on the ``_now`` clock): ``ttft_s = first_token_s - arrival_s``;
    per-token decode latency = ``(done_s - first_token_s) / (n - 1)``."""

    rid: int
    tokens: np.ndarray
    arrival_s: float
    admitted_s: float
    first_token_s: float
    done_s: float
    # per-token ``_now`` stamps (first token included): consecutive
    # differences are the inter-token latency samples the disagg bench's
    # decode-p99 floor is computed from
    token_times: tuple = ()

    @property
    def n_tokens(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def intervals_s(self) -> tuple:
        """Inter-token gaps (seconds), one per decode token."""
        return tuple(
            b - a for a, b in zip(self.token_times, self.token_times[1:])
        )

    @property
    def ttft_s(self) -> float:
        return self.first_token_s - self.arrival_s

    @property
    def per_token_s(self) -> float:
        if self.n_tokens <= 1:
            return 0.0
        return (self.done_s - self.first_token_s) / (self.n_tokens - 1)


class ServingEngine:
    def __init__(
        self,
        params,
        cfg: TransformerConfig,
        pcfg: PagedCacheConfig,
        bcfg: BatcherConfig | None = None,
        metrics: MetricsRegistry | None = None,
        fused: bool = True,
        decode_impl: str = "jnp",
        slo_window_s: float = 10.0,
    ):
        self.params = params
        self.cfg = cfg
        self.pcfg = pcfg
        self.bcfg = bcfg or BatcherConfig()
        self.fused = bool(fused)
        # accepted and checked (make_paged_decode_fn), selects nothing:
        # ops.paged_attention picks its path from the backend and shapes
        self.decode_impl = decode_impl
        # the engine's accounting lives in a metrics registry (shareable —
        # the replica pool passes one per replica so its report is a view
        # over the same counters); per-request timestamps stay on
        # CompletedRequest, the registry carries the aggregates
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # TTFT carries the SLO, so it is the WINDOWED histogram: the
        # cumulative view dilutes a fresh breach after a quiet hour, the
        # rolling window over slo_window_s is what engine.report() shows
        # AND what the pool arbiter's breach check reads — one instrument,
        # created here so no later plain histogram() call can shadow it
        self.slo_window_s = float(slo_window_s)
        self.metrics.windowed_histogram(
            "serve.ttft_ms", interval_s=self.slo_window_s / 10.0, intervals=10
        )
        # positions a pass computes a slot: 1, or a diffusion block's B; and
        # what a round is, the batcher's kind with what the engine runs for
        # it: one token a slot, or a diffusion block's pass
        self.block_len = block_of(cfg).block_len(cfg)
        self._kind = _Tokens()
        if self.block_len > 1:
            if pcfg.block_size % self.block_len:
                raise ValueError(
                    f"a pool block of {pcfg.block_size} positions holds no "
                    f"whole number of diffusion blocks of {self.block_len}"
                )
            if self.bcfg.admission != "reserve":
                raise NotImplementedError(
                    "preemption of a slot with an open diffusion block (its "
                    "block and phase) is not implemented: build the engine "
                    "with BatcherConfig(admission='reserve')"
                )
            self._kind = _Blocks(self.block_len, cfg.denoise_steps, cfg.mask_id)
        self.batcher = ContinuousBatcher(pcfg, self.bcfg, self._kind)
        if self.batcher.prefix_index is not None and not isinstance(
            cfg, TransformerConfig
        ):
            # a hit runs models.generate.prefill_suffix, the dense block's
            raise NotImplementedError(
                f"the prefix cache is not implemented for "
                f"{type(cfg).__name__}: build the engine with "
                f"BatcherConfig(prefix_cache=False)"
                + (
                    " (its layers hold a state a slot, and a prefix's "
                    "state is a snapshot nobody has taken)"
                    if slot_parts(cfg) else ""
                )
            )
        self.pools = init_pools(cfg, pcfg)
        # what the layers hold a slot and not a position (a recurrent
        # layer's state); {} for a block that holds nothing so
        self.state = init_state(cfg, self.bcfg.slots)
        # what one cached position takes over all the layers, what a slot
        # holds whatever its length, and the layers that hold such a state
        # (ft.engine.decode_dispatch, report())
        self.cache_bytes_per_position = cache_bytes_per_position(cfg)
        self.state_bytes_per_slot = state_bytes_per_slot(cfg)
        self.state_layers = max(
            (layers for _, layers in slot_parts(cfg).values()), default=0
        )
        # those of them whose update runs the Pallas kernel, fixed when the
        # decode program is built, as ``attn_kernel_layers`` below
        self.state_kernel_layers = block_of(cfg).state_kernel_layers(cfg)[1]
        # the decode program's expert layers and those of them whose
        # grouped products run the Pallas kernel, fixed likewise
        self.expert_layers, self.expert_kernel_layers = block_of(
            cfg
        ).expert_kernel_layers(cfg, self.bcfg.slots)
        # donation keeps steady-state decode allocation-free: the pool
        # scatter aliases in place instead of copying the whole pool every
        # round.  XLA:TPU aliases every donated pool buffer (AOT compile
        # of the flagship decode for v5e: alias_size == the pools' 1.0 GiB)
        # and so does XLA:CPU, warning-free — pools enter and leave on one
        # device with one shape, the case JAX's donation matching needs
        self._decode = make_paged_decode_fn(
            cfg, donate=True, fused=self.fused, impl=decode_impl
        )
        # how often the kernel engages: attention layers in the decode
        # program and those of them that run the Pallas kernel, fixed
        # when the program is built (ft.engine.decode_dispatch, report())
        self.attn_layers, self.attn_kernel_layers = decode_attention_layers(
            cfg, pcfg, self.fused
        )
        # a named ``def`` so that the program shows in a profile as
        # ``jit_prefill_program``, where the benchmark finds it by name
        def prefill_program(p, tok):
            return prefill(p, tok, cfg, max_len=pcfg.max_len)

        self._prefill = jax.jit(prefill_program)
        # the greedy pick runs where the logits are, so that (S,) ids and
        # not (S, V) logits cross to the host: two shapes for the server
        # lifetime, the decode round's (S, V) and a prefill's (1, V)
        self._greedy_ids = jax.jit(greedy_ids)
        # a diffusion pass's pick: the reveal, on the device, folded there
        # into the next pass's open blocks; the reveal alone is a view of
        # the same program
        self._pass_pick = jax.jit(unmask_greedy_ids, static_argnames="mask_id")
        self._unmask = _RevealView(
            self._pass_pick, getattr(self._kind, "mask_id", 0)
        )
        # suffix-only prefill for prefix-cache hits, fused with the block
        # gather into ONE program: one compile per (chain_len, cached_len,
        # suffix_len) bucket — the prefix shapes carry the offset, so
        # RoPE/mask come out right with zero dynamic indexing, and the
        # per-layer gather never round-trips through eager dispatch (which
        # costs more than the tokens it saves at small model sizes)
        def _hit(p, tok, pools, chain, c):
            view = gather_seq(pools, chain, length=c)
            return prefill_suffix(
                p, tok,
                {
                    "k": [k[None] for k in view["k"]],
                    "v": [v[None] for v in view["v"]],
                },
                cfg, max_len=pcfg.max_len,
            )

        self._hit_prefill = jax.jit(_hit, static_argnums=(4,))
        self._write = jax.jit(write_prefill, donate_argnums=(0,))
        self._write_state = jax.jit(write_state, donate_argnums=(0,))
        # the suffix scatter never touches blocks below start_block — the
        # shared cached blocks stay byte-identical through a hit
        self._write_at = jax.jit(
            write_prefill_at, static_argnums=(3,), donate_argnums=(0,)
        )
        self._write_back = jax.jit(write_swapped, donate_argnums=(0,))
        # migrated-KV import scatter: block-shaped arrays straight into
        # the pool (one compile per distinct migrated block count)
        self._write_import = jax.jit(write_imported, donate_argnums=(0,))
        self._keys: dict = {}  # slot -> presplit (max_new, 2) key rows
        # rid -> blocks held for an in-flight migration export; released
        # on the decode side's ack (or the abort path), NEVER before —
        # the bytes on the wire are a VIEW of these blocks until the
        # receiver confirms it owns a copy
        self._exported: dict = {}
        self.completed: dict = {}
        self.steps = 0
        self.decode_steps = 0
        # the round (a diffusion pass) the last step dispatched ahead: the
        # next step's own
        self._ahead: _Round | None = None
        self.metrics.counter("serve.rounds_ahead")
        # windowed prefix hit-rate over the SLO window (admissions only;
        # exported as a gauge so `obs metrics DIR --prom` carries it)
        self._prefix_window: deque = deque()
        if self.batcher.prefix_index is not None:
            self.batcher.prefix_index.on_evict = self._on_prefix_evict

    @classmethod
    def from_config(cls, config: dict, pcfg: PagedCacheConfig,
                    bcfg: BatcherConfig | None = None, *, seed: int = 0,
                    **kwargs) -> "ServingEngine":
        """The engine for a model given as a configuration: a
        ``model_type`` and its published keys (``models.configs``), with
        parameters made on the device from ``seed``.  How a model is
        chosen; every other argument is the constructor's."""
        cfg = config_from_dict(config)
        params = init_model_params(jax.random.PRNGKey(seed), cfg)
        jax.block_until_ready(params)
        return cls(params, cfg, pcfg, bcfg, **kwargs)

    # ---- intake ------------------------------------------------------------

    def submit(self, request: Request) -> bool:
        """Queue a request (stamping arrival if the caller didn't)."""
        if request.arrival_s == 0.0:
            request = dataclasses.replace(request, arrival_s=_now())
        ok = self.batcher.submit(request)
        self.metrics.counter(
            "serve.submitted" if ok else "serve.rejected"
        ).inc()
        if not ok:
            record_event(
                "serve_reject", rid=request.rid,
                reason=self.batcher.rejected[-1][1],
            )
        return ok

    @property
    def idle(self) -> bool:
        return self.batcher.idle

    # ---- the scheduling round ----------------------------------------------

    def step(self) -> dict:
        """One resume → admit → grow → decode → retire round; returns
        counters.  Growth (on-demand admission only) allocates each
        active sequence's next decode block; exhaustion preempts the
        newest resident sequence (swap-out or recompute per
        ``BatcherConfig.preempt``) until the rest fit.

        The step owns one decode round: the one the last step dispatched
        ahead, or one it dispatches now over the active slots.  It may
        dispatch the round after its own ahead (see the module's
        docstring); every token of its own round is recorded when it
        returns."""
        t0 = _now()
        with span("ft.engine.round", round=self.steps):
            with span("ft.engine.resume"):
                resumed = self.batcher.try_resume(t0)
                for slot, state, kv in resumed:
                    self._resume_slot(slot, state, kv)
            with span("ft.batcher.try_admit"):
                admitted = self.batcher.try_admit(t0)
            if self.batcher.admit_blocked is not None:
                rid, want, free = self.batcher.admit_blocked
                self.metrics.counter("serve.admit_blocked").inc()
                record_event(
                    "serve_admit_blocked", rid=rid, want=want, free=free
                )
            for slot, state in admitted:
                record_event(
                    "serve_admit", rid=state.rid, slot=slot,
                    prompt_len=state.request.prompt_len,
                    blocks=len(state.block_ids),
                )
                with span(
                    "ft.engine.prefill", rid=state.rid,
                    prompt_len=state.request.prompt_len,
                    cached_tokens=state.cached_tokens,
                    state_bytes=self.state_bytes_per_slot,
                ):
                    self._prefill_slot(slot, state)
            with span("ft.engine.grow"):
                preempted = self._grow_with_preemption()
            own, self._ahead = self._ahead, None
            active = self.batcher.active_slots() if own is None else own.active
            ahead = 0
            if own is None and active:
                with span("ft.batcher.batch_arrays"):
                    arrays = self.batcher.batch_arrays()
                own = self._dispatch(self.steps, active, arrays)
            if own is not None:
                if not own.delivered and self.batcher.continues(active):
                    # the next round is known before this one's ids reach
                    # the host, but for what it takes of them: the ids, or
                    # a pass's reveal folded in, on the device
                    self._ahead = self._dispatch(
                        self.steps + 1, active,
                        self.batcher.batch_arrays(ahead=1), own,
                    )
                    self.metrics.counter("serve.rounds_ahead").inc()
                    ahead = len(active)
                self._deliver(own)
            moe, blocks, emitted = (
                ({}, {}, 0) if own is None
                else (own.moe, own.blocks, own.emitted)
            )
            with span("ft.engine.retire"):
                finished = self.batcher.retire_ready()
                for slot, state in finished:
                    self._keys.pop(slot, None)
                    self._complete(state)
            free = self.batcher.allocator.num_free
            total = self.pcfg.num_blocks - 1
            # an annotation takes its values when it opens: the last span
            # of the round, opened once its counts are known, carries them
            with span(
                "ft.engine.bookkeeping", round=self.steps,
                decoded=len(active), ahead=ahead, admitted=len(admitted),
                finished=len(finished), blocks_in_use=total - free,
                blocks_total=total,
                state_slots_live=self.batcher.num_active if self.state else 0,
                **moe, **blocks,
            ):
                self.steps += 1
                m = self.metrics
                if moe:
                    m.counter("serve.moe_picks").inc(moe["picks"])
                    m.counter("serve.moe_local_picks").inc(moe["local_picks"])
                m.counter("serve.rounds").inc()
                m.counter("serve.admitted").inc(len(admitted))
                m.counter("serve.finished").inc(len(finished))
                m.gauge("serve.active_slots").set(self.batcher.num_active)
                m.gauge("serve.free_blocks").set(free)
                m.gauge("serve.active_blocks").set(total - free)
                m.gauge("serve.preempted_seqs").set(
                    len(self.batcher.preempted)
                )
                m.histogram(
                    "serve.cache_occupancy", buckets=_OCCUPANCY_BUCKETS
                ).observe((total - free) / total)
        return {
            "admitted": len(admitted),
            "resumed": len(resumed),
            "preempted": preempted,
            "decoded": len(active),
            "finished": len(finished),
            # tokens delivered to requests this round: ``decoded`` for a
            # block of one token a slot, a diffusion block's whole blocks
            "emitted": emitted,
        }

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise RuntimeError(f"engine not idle after {max_steps} steps")

    # ---- internals ---------------------------------------------------------

    def _program_counts(self) -> dict:
        """What the decode program is, fixed when it is built: the layers
        of each kind and those that run a Pallas kernel, and what the pools
        and the state hold (``ft.engine.decode_dispatch``, ``report()``)."""
        return {
            "attn_layers": self.attn_layers,
            "attn_kernel_layers": self.attn_kernel_layers,
            "cache_bytes_per_position": self.cache_bytes_per_position,
            "state_bytes_per_slot": self.state_bytes_per_slot,
            "state_layers": self.state_layers,
            "state_kernel_layers": self.state_kernel_layers,
            "expert_layers": self.expert_layers,
            "expert_kernel_layers": self.expert_kernel_layers,
        }

    def _dispatch(self, rid: int, active, arrays,
                  prev: _Round | None = None) -> _Round:
        """Dispatch round ``rid`` over the ``active`` slots, its decode
        program and then its pick, and fetch nothing.  ``arrays``:
        ``batch_arrays``'s; ``prev``: the round in flight, not fetched yet,
        where this one goes ahead of it (it then takes ``prev.feed``, on
        the device)."""
        with span(
            "ft.engine.decode_dispatch", round=rid, active=len(active),
            ahead=int(prev is not None), **self._program_counts(),
            **self._kind.span_args(self),
        ):
            logits, fetch, counts, feed = self._kind.dispatch(
                self, arrays, None if prev is None else prev.feed
            )
        # counted while the device decodes
        sampled = sum(
            self.batcher.slots[slot].request.temperature > 0
            for slot in active
        )
        return _Round(rid, active, fetch, counts, feed, sampled,
                      logits if sampled else None)

    def _deliver(self, rnd: _Round) -> None:
        """Fetch a round's ids (and a pass's revealed places) and record
        what it did, once."""
        if rnd.delivered:
            return
        active, sampled = rnd.active, rnd.sampled
        with span("ft.engine.decode_fetch", round=rnd.rid):
            # the logits stay on the device and are dropped with the
            # round.  The routers' counts come back beside the ids, in the
            # same fetch
            if rnd.counts is None:
                fetched = [np.asarray(a) for a in rnd.fetch]
            else:
                *fetched, counts = jax.device_get((*rnd.fetch, rnd.counts))
                rnd.moe = {k: int(v) for k, v in zip(MOE_COUNTS, counts)}
        now = _now()
        with span(
            "ft.engine.sample", on_device=len(active) - sampled,
            rows_fetched=sampled, active=len(active),
        ):
            rnd.emitted, rnd.blocks = self._kind.record(self, rnd, fetched, now)
        rnd.delivered, rnd.logits = True, None
        self.decode_steps += 1
        self.metrics.counter("serve.decode_tokens").inc(rnd.emitted)
        record_event("serve_decode", n_active=len(active))

    def settle(self) -> None:
        """Deliver the round (or diffusion pass) in flight, if there is
        one: fetch its ids and record its tokens.  Whatever reads or moves
        a slot outside a round calls it first (a preemption, a migration in
        or out, a prefix handoff; a resume never meets a round in flight,
        since none is dispatched ahead while a sequence waits to resume);
        the step that owns the round still retires and counts it."""
        if self._ahead is not None:
            self._deliver(self._ahead)

    def _decode_round(self, tables, lengths, tokens) -> tuple:
        """Dispatch the decode program over the pools (and the state,
        where the block holds one: a sixth argument, donated like the
        pools, and the program's last result) and keep what it hands back.
        Returns ``(logits[, what the routers did])``."""
        carried = (self.state,) if self.state else ()
        logits, self.pools, *rest = self._decode(
            self.params, self.pools, tables, lengths, tokens, *carried
        )
        if carried:
            self.state = rest.pop()
        return (logits, *rest)

    def _write_slot(self, slot: int, cache: dict, block_ids) -> None:
        """Put a prefill's ``cache`` in place: its rows into ``block_ids``
        and, where the block holds a state a slot, its final state into
        ``slot``'s (replacing whatever the slot's last sequence left)."""
        self.pools = self._write(
            self.pools, cache, np.asarray(block_ids, np.int32)
        )
        if self.state:
            self.state = self._write_state(
                self.state, cache["state"], np.int32(slot)
            )
            self.metrics.counter("serve.state_resets").inc()

    def _grow_with_preemption(self) -> int:
        """On-demand growth with the exhaustion → preempt loop: keep
        evicting the newest resident sequence until every survivor's next
        decode block allocates.  Returns how many sequences were
        preempted this round; raises when a lone sequence cannot grow
        (nothing left to evict — submit()'s pool-capacity guard makes
        that unreachable for admissible requests)."""
        preempted = 0
        while True:
            try:
                self.batcher.grow_for_decode()
                return preempted
            except CacheExhausted:
                victim = self.batcher.pick_victim()
                if victim is None:
                    raise
                self._preempt_slot(victim)
                preempted += 1

    def _preempt_slot(self, slot: int) -> None:
        self.settle()
        state = self.batcher.slots[slot]
        mode = self.bcfg.preempt
        kv = None
        if mode == "swap":
            # host copies of the written positions — np.asarray moves the
            # bytes off-device NOW, before the freed blocks are rewritten
            # (and of the slot's state, whole, whatever the length)
            kv = jax.tree.map(np.asarray, {
                "rows": gather_seq(
                    self.pools, state.block_ids, length=state.length
                ),
                "state": read_state(self.state, slot),
            })
            swapped = sum(a.nbytes for a in jax.tree.leaves(kv))
            self.metrics.counter("serve.swap_out_bytes").inc(swapped)
            self.metrics.counter("serve.state_swap_bytes").inc(
                sum(a.nbytes for a in jax.tree.leaves(kv["state"]))
            )
            self.metrics.counter("serve.swap_outs").inc()
            record_event(
                "serve_swap_out", rid=state.rid, length=state.length,
                bytes=swapped,
            )
        blocks = len(state.block_ids)
        self.batcher.preempt(slot, kv)
        self._keys.pop(slot, None)  # re-derived from the seed on resume
        self.metrics.counter("serve.preempts").inc()
        record_event(
            "serve_preempt", rid=state.rid, slot=slot, mode=mode,
            length=state.length, blocks_freed=blocks,
            n_generated=len(state.generated),
        )

    def _resume_slot(self, slot: int, state: SeqState, kv) -> None:
        req = state.request
        n = len(state.block_ids)
        bs = self.pcfg.block_size
        if kv is not None:
            # swap-in: scatter the exact saved bytes back (zero-padded to
            # whole blocks; the pad sits past the causal bound, invisible
            # until overwritten) — resume is bit-identical by construction
            def pad(a):
                full = np.zeros((n * bs, *a.shape[1:]), a.dtype)
                full[: a.shape[0]] = a
                return jnp.asarray(full)

            padded = jax.tree.map(pad, kv["rows"])
            self.pools = self._write_back(
                self.pools, padded, np.asarray(state.block_ids, np.int32)
            )
            if self.state:
                self.state = self._write_state(
                    self.state, kv["state"], np.int32(slot)
                )
        else:
            # recompute: replay the tokens whose K/V were dropped (prompt
            # + already-written decode tokens) through prefill
            written = np.concatenate([
                np.asarray(req.prompt, np.int32),
                np.asarray(
                    state.generated[: state.length - req.prompt_len],
                    np.int32,
                ),
            ])
            _, cache = self._prefill(self.params, written[None])
            self._write_slot(slot, cache, state.block_ids)
        if req.temperature > 0:
            # same derivation as _prefill_slot: the schedule is a pure
            # function of the seed, indexed by len(generated) — resume
            # continues exactly where the evicted slot stopped
            self._keys[slot] = jax.random.split(
                jax.random.PRNGKey(req.seed), req.max_new_tokens
            )
        self.metrics.counter("serve.resumes").inc()
        record_event(
            "serve_resume", rid=state.rid, slot=slot,
            mode="swap" if kv is not None else "recompute",
            length=state.length, blocks=n,
        )

    def _cost_params(self):
        params = getattr(self, "_cost_params_cache", None)
        if params is None:
            from ..planner.calibrate import default_params

            params = self._cost_params_cache = default_params()
        return params

    def _on_prefix_evict(self, block: int) -> None:
        self.metrics.counter("serve.prefix_evictions").inc()
        record_event("serve_prefix_evict", block=int(block))

    def _note_prefix_admission(self, hit: bool, now: float) -> None:
        """One admission's hit/miss into the windowed hit-rate gauge."""
        w = self._prefix_window
        w.append((now, 1 if hit else 0))
        cutoff = now - self.slo_window_s
        while w and w[0][0] < cutoff:
            w.popleft()
        self.metrics.gauge("serve.prefix_hit_rate").set(
            sum(h for _, h in w) / len(w)
        )

    def release_prefix_cache(self) -> int:
        """Drop every index-held block reference (the drain/leak-check
        path: afterwards the free list must be whole again once no
        sequences are resident).  Returns how many entries were
        released."""
        idx = self.batcher.prefix_index
        return idx.clear() if idx is not None else 0

    # ---- prefill/decode disaggregation -------------------------------------

    def prefill_for_migration(self, request: Request, codec: str = "f32"):
        """The PREFILL replica's half of a migration: run the prompt's
        prefill, emit the first token (greedy — the RPC tier carries no
        sampling knobs), and pack the sequence's KV blocks for the wire.

        The blocks stay allocated under ``_exported[rid]`` until
        :meth:`release_exported` — the ack/abort discipline: releasing
        before the decode side confirms admission would let a concurrent
        prefill recycle the blocks while their bytes are still the only
        copy of this sequence's state.  Returns ``None`` when the pool
        cannot hold the prompt right now (the caller refuses the request
        back to the front door); raises :class:`MigrationError` for a
        request that could NEVER migrate (oversized, sampled)."""
        req = request
        self._refuse_diffusion_migration(req)
        self.settle()
        if req.temperature > 0:
            raise MigrationError(
                f"request {req.rid}: migration is greedy-only "
                f"(temperature={req.temperature})"
            )
        if req.prompt_len < 1 or req.prompt_len >= self.pcfg.max_len:
            raise MigrationError(
                f"request {req.rid}: prompt_len {req.prompt_len} outside "
                f"(0, max_len={self.pcfg.max_len})"
            )
        if req.rid in self._exported:
            raise MigrationError(
                f"request {req.rid}: migration already in flight"
            )
        n = self.pcfg.blocks_for(req.prompt_len)
        t0 = _now()
        try:
            blocks = self.batcher._alloc_with_evict(n)
        except CacheExhausted:
            self.metrics.counter("serve.migration_export_blocked").inc()
            return None
        record_event(
            "serve_admit", rid=req.rid, slot=-1,
            prompt_len=req.prompt_len, blocks=n, migration=True,
        )
        prompt = np.asarray(req.prompt, np.int32)
        logits, cache = self._prefill(self.params, prompt[None])
        self.pools = self._write(
            self.pools, cache, np.asarray(blocks, np.int32)
        )
        first_token = int(np.asarray(self._greedy_ids(logits))[0])
        kv = jax.tree.map(np.asarray, export_blocks(self.pools, blocks))
        # no slot was taken: what the sequence carries a slot goes out as
        # the prefill left it, one array a layer
        carried = {
            part: [np.asarray(a[0]) for a in layers]
            for part, layers in cache.get("state", {}).items()
        }
        meta, blob = pack_kv(kv, codec=codec, state=carried)
        self._exported[req.rid] = blocks
        now = _now()
        self.metrics.counter("serve.migration_exports").inc()
        self.metrics.histogram(
            "serve.migration_bytes", buckets=_MIGRATION_BYTES_BUCKETS
        ).observe(len(blob))
        self.metrics.histogram("serve.ttft_ms").observe(
            (now - req.arrival_s) * 1e3
        )
        self._record_prefill(req, -1, 0, now - t0)
        return {
            "first_token": first_token,
            "meta": meta,
            "blob": blob,
            "ttft_s": now - req.arrival_s,
            "prefill_s": now - t0,
        }

    def _refuse_diffusion_migration(self, req: Request) -> None:
        if self.block_len > 1:
            raise MigrationError(
                f"request {req.rid}: migration of a slot with an open "
                f"diffusion block is not implemented (its block and phase "
                f"are no part of the payload)"
            )

    def _payload_geometry(self) -> dict:
        """What a migration payload has to state to land here: the pool's
        block size, the rows' layout and the layers that cache them, the
        state's layout and the layers that hold it (``models.configs.
        position_parts``, ``slot_parts``)."""
        rows, held = position_parts(self.cfg), slot_parts(self.cfg)
        return {
            "block_size": self.pcfg.block_size,
            "layout": {k: list(row) for k, (row, _) in rows.items()},
            "n_layers": max((n for _, n in rows.values()), default=0),
            "state": {k: list(shape) for k, ((shape, _), _) in held.items()},
            "state_layers": self.state_layers,
        }

    def release_exported(self, rid: int, acked: bool) -> bool:
        """Drop the blocks held for ``rid``'s migration export — on the
        decode side's ACK (the handoff succeeded, the receiver owns a
        copy) or on the ABORT path (refused, timed out, receiver died;
        the request goes back to the front door's retry loop).  Exactly
        one release per export, loud counters either way."""
        blocks = self._exported.pop(rid, None)
        if blocks is None:
            return False
        self.batcher.allocator.free(blocks)
        self.metrics.counter(
            "serve.migration_acked" if acked else "serve.migration_aborted"
        ).inc()
        if not acked:
            record_event("serve_migration_abort", rid=rid,
                         blocks=len(blocks))
        return True

    def admit_migrated(self, request: Request, first_token: int,
                       meta: dict, blob: bytes):
        """The DECODE replica's half: verify the payload, land the
        sequence.  Refuse-don't-guess — :class:`MigrationError` for any
        integrity or geometry violation (CRC, shapes, a block count that
        does not match the prompt), ``None`` for a clean capacity
        refusal (no slot / no blocks / resume backlog; the prefill side
        aborts and the front door retries).  On success the sequence is
        resident exactly as if prefill had run locally — length =
        prompt_len, first token recorded, decode continues from the
        imported blocks on the next :meth:`step`."""
        req = request
        self._refuse_diffusion_migration(req)
        self.settle()
        total = req.prompt_len + req.max_new_tokens
        if req.prompt_len < 1 or total > self.pcfg.max_len:
            raise MigrationError(
                f"request {req.rid}: prompt+max_new {total} exceeds "
                f"max_len {self.pcfg.max_len}"
            )
        if self.pcfg.blocks_for(total) > self.pcfg.num_blocks - 1:
            raise MigrationError(
                f"request {req.rid}: needs {self.pcfg.blocks_for(total)} "
                f"blocks, pool holds {self.pcfg.num_blocks - 1}"
            )
        kv = unpack_kv(meta, blob)  # CRC + per-tensor verification
        carried = unpack_state(meta, blob)
        stated = meta.get("state") or {"layout": {}, "n_layers": 0}
        shipped = {
            "block_size": int(meta["block_size"]), "layout": meta["layout"],
            "n_layers": int(meta["n_layers"]),
            "state": stated["layout"], "state_layers": int(stated["n_layers"]),
        }
        if shipped != self._payload_geometry():
            raise MigrationError(
                f"request {req.rid}: payload geometry (block size, layout "
                f"and layers of the rows, of the state) {shipped} does not "
                f"match this replica's model {self._payload_geometry()}"
            )
        n_mig = int(meta["n_blocks"])
        if n_mig != self.pcfg.blocks_for(req.prompt_len):
            raise MigrationError(
                f"request {req.rid}: {n_mig} migrated blocks for a "
                f"{req.prompt_len}-token prompt "
                f"(expected {self.pcfg.blocks_for(req.prompt_len)})"
            )
        now = _now()
        admit = self.batcher.admit_migrated(req, first_token, now)
        if admit is None:
            self.metrics.counter("serve.migration_refused").inc()
            record_event(
                "serve_migration_refuse", rid=req.rid, reason="capacity"
            )
            return None
        slot, state = admit
        kv_dev = jax.tree.map(lambda a: jnp.asarray(a, self.cfg.dtype), kv)
        self.pools = self._write_import(
            self.pools, kv_dev, np.asarray(state.block_ids[:n_mig], np.int32)
        )
        if self.state:
            self.state = self._write_state(
                self.state, jax.tree.map(lambda a: a[None], carried),
                np.int32(slot),
            )
        if self.batcher.prefix_index is not None:
            # mid-stream arrival of already-full blocks: the prompt's
            # FULL blocks are shareable the moment they land, so the
            # index adopts them at admission, not at retirement (the
            # retirement insert walks the same chain idempotently)
            full = req.prompt_len // self.pcfg.block_size
            self.batcher.prefix_index.insert(
                np.asarray(req.prompt), state.block_ids[:full]
            )
        self.metrics.counter("serve.migrations_in").inc()
        self.metrics.histogram(
            "serve.migration_bytes", buckets=_MIGRATION_BYTES_BUCKETS
        ).observe(len(blob))
        record_event(
            "serve_migration_recv", rid=req.rid, slot=slot,
            bytes=len(blob), codec=str(meta.get("codec")), blocks=n_mig,
        )
        return slot

    # ---- prefix-warm drain handoff -----------------------------------------

    def _block_hash(self, block: int) -> str:
        """CRC32 over a block's bytes across every part and layer — the
        content witness a handoff successor checks its RECOMPUTED block
        against (block bytes are a pure function of the token prefix, so
        agreeing hashes mean the warm cache really is the same cache)."""
        import zlib

        crc = 0
        for layers in self.pools.values():
            for layer in layers:
                crc = zlib.crc32(np.asarray(layer[block]).tobytes(), crc)
        return f"{crc & 0xFFFFFFFF:08x}"

    def export_prefix_handoff(self) -> dict | None:
        """Serialize the prefix index for a drain handoff: every node as
        its root-to-node token prefix plus the content hash of its block.
        Token ids and hashes travel; block ids and raw K/V bytes never do
        — the successor RECOMPUTES each block from the prefix and uses
        the hash to prove it rebuilt the same bytes.  Returns ``None``
        when the prefix cache is disabled."""
        idx = self.batcher.prefix_index
        if idx is None:
            return None
        self.settle()
        entries = [
            {
                "prefix": [int(t) for key in path for t in key],
                "hash": self._block_hash(block),
            }
            for path, block in idx.node_paths()
        ]
        self.metrics.counter("serve.handoff_exported_blocks").inc(
            len(entries)
        )
        record_event("serve_handoff_export", entries=len(entries))
        return {
            "version": 1,
            "block_size": self.pcfg.block_size,
            "entries": entries,
        }

    def prewarm_prefix_from_handoff(self, doc) -> dict:
        """Rebuild a predecessor's prefix cache from its handoff export:
        recompute each prefix's last block via prefill, verify the bytes
        against the recorded content hash, and adopt verified blocks into
        this replica's index BEFORE traffic arrives.  A hash mismatch
        refuses that entry (and, since children need their parent chain,
        its whole subtree) — a corrupt handoff degrades to a cold start,
        never to serving wrong K/V.  Returns stats counters."""
        stats = {"inserted": 0, "skipped": 0, "hash_mismatches": 0,
                 "refused": None}
        idx = self.batcher.prefix_index
        if idx is None:
            stats["refused"] = "prefix cache disabled"
            return stats
        self.settle()
        bs = self.pcfg.block_size
        if (
            not isinstance(doc, dict)
            or doc.get("version") != 1
            or int(doc.get("block_size", -1)) != bs
            or not isinstance(doc.get("entries"), list)
        ):
            stats["refused"] = "incompatible handoff payload"
            self.metrics.counter("serve.handoff_refused").inc()
            record_event("serve_handoff_refused",
                         reason=stats["refused"])
            return stats
        alloc = self.batcher.allocator
        # parents sort before their children (tuple-prefix order), so a
        # single pass builds chains bottom-up; keep one sequence's worth
        # of blocks free so prewarming can never starve first admission
        reserve = self.pcfg.blocks_per_seq
        for e in sorted(doc["entries"], key=lambda e: len(e["prefix"])):
            prefix = e.get("prefix")
            if (
                not isinstance(prefix, list) or not prefix
                or len(prefix) % bs != 0
            ):
                stats["skipped"] += 1
                continue
            tokens = np.asarray(prefix, np.int32)
            n = len(prefix) // bs
            matched = idx.match(tokens)
            if len(matched) >= n:
                continue  # already warm (shared parent of two subtrees)
            if len(matched) < n - 1:
                stats["skipped"] += 1  # parent refused/missing upstream
                continue
            if alloc.num_free <= reserve:
                stats["skipped"] += 1
                continue
            [b] = alloc.alloc(1)
            _, cache = self._prefill(self.params, tokens[None])
            self.pools = self._write_at(
                self.pools, cache, np.asarray([b], np.int32), n - 1
            )
            want = e.get("hash")
            if want is not None and self._block_hash(b) != want:
                alloc.release([b])
                stats["hash_mismatches"] += 1
                self.metrics.counter("serve.handoff_hash_mismatch").inc()
                record_event(
                    "serve_handoff_hash_mismatch", prefix_len=len(prefix)
                )
                continue
            idx.insert(tokens, matched + [b])
            alloc.release([b])  # the index's retain is now the holder
            stats["inserted"] += 1
        self.metrics.counter("serve.handoff_prewarmed_blocks").inc(
            stats["inserted"]
        )
        record_event("serve_handoff_prewarm", **stats)
        return stats

    def _record_prefill(self, req: Request, slot: int, cached: int,
                        measured_s: float) -> None:
        """The ``serve_prefill`` event: a prefill's measured time beside
        the cost model's prediction.  The prediction is the event's alone,
        so nothing of it is computed while no recorder is installed."""
        if current_recorder() is None:
            return
        from .costs import predict_prefill_us

        record_event(
            "serve_prefill", rid=req.rid, slot=slot,
            prompt_len=req.prompt_len, cached_tokens=cached,
            measured_us=round(measured_s * 1e6, 3),
            predicted_us=round(
                predict_prefill_us(
                    self.cfg, req.prompt_len, self._cost_params(),
                    cached_tokens=cached,
                ),
                3,
            ),
        )

    def _prefill_slot(self, slot: int, state: SeqState) -> None:
        t0 = _now()
        req = state.request
        prompt = np.asarray(req.prompt, np.int32)
        c = state.cached_tokens
        if self.block_len > 1:
            # the prompt's whole blocks, committed; its tail is in the
            # first open block, and the first token comes with that block
            if state.length:
                with span("ft.engine.prefill_dispatch", rid=req.rid):
                    _, cache = self._prefill(
                        self.params, prompt[None, : state.length]
                    )
                    self._write_slot(slot, cache, state.block_ids)
                # waited for here, as the other blocks wait for a first
                # token's fetch: ``ft.engine.prefill`` spans the prefill's
                # device time, not its dispatch alone
                jax.block_until_ready(self.pools)
            self._record_prefill(req, slot, c, _now() - t0)
            return
        with span("ft.engine.prefill_dispatch", rid=req.rid):
            logits = self._dispatch_prefill(slot, state, prompt, c)
            ids = self._greedy_ids(logits)
        if self.batcher.prefix_index is not None:
            self._note_prefix_admission(c > 0, t0)
        if req.temperature > 0:
            if req.seed is None:  # unreachable via submit(); guard direct use
                raise ValueError(
                    f"request {req.rid}: temperature > 0 requires seed="
                )
            # the SAME presplit schedule generate() uses, so a sampled
            # request reproduces generate(key=PRNGKey(seed)) exactly
            self._keys[slot] = jax.random.split(
                jax.random.PRNGKey(req.seed), req.max_new_tokens
            )
        with span("ft.engine.prefill_fetch_sample"):
            tok = self._pick(slot, np.asarray(ids), logits, 0)
        now = _now()
        self.batcher.record_first_token(slot, tok, now)
        self.metrics.histogram("serve.ttft_ms").observe(
            (now - req.arrival_s) * 1e3
        )
        self._record_prefill(req, slot, c, now - t0)

    def _dispatch_prefill(self, slot: int, state: SeqState, prompt, c: int):
        """Dispatch the prompt's prefill (the suffix alone on a
        prefix-cache hit) and the scatter of its K/V into the sequence's
        blocks; returns the logits, still on the device."""
        req = state.request
        if c > 0:
            bs = self.pcfg.block_size
            # the prefix K/V lives in the shared blocks — plus, for a
            # full-prompt hit, the COW fork's SOURCE (the fresh fork
            # destination in block_ids holds garbage until the scatter
            # below fills it with the same bytes)
            chain = list(state.block_ids[: state.shared_blocks])
            if state.cow_src is not None:
                chain.append(state.cow_src)
            logits, cache = self._hit_prefill(
                self.params, prompt[None, c:], self.pools,
                np.asarray(chain, np.int32), c,
            )
            # scatter ONLY from the first non-shared block onward: the
            # cache's positions there are the gathered prefix bytes (for
            # the COW fork's mid-block head) plus the freshly computed
            # suffix K/V; the shared blocks below are never rewritten
            sb = c // bs
            self.pools = self._write_at(
                self.pools, cache,
                np.asarray(state.block_ids[sb:], np.int32), sb,
            )
            if state.cow_src is not None:
                self.metrics.counter("serve.prefix_cow").inc()
                record_event(
                    "serve_prefix_cow", rid=req.rid,
                    src=int(state.cow_src), dst=int(state.block_ids[sb]),
                )
                self.batcher.allocator.release([state.cow_src])
                state.cow_src = None
            self.metrics.counter("serve.prefix_hits").inc()
            self.metrics.counter("serve.cached_tokens_saved").inc(c)
            record_event(
                "serve_prefix_hit", rid=req.rid, cached_tokens=c,
                shared_blocks=state.shared_blocks,
                suffix_tokens=req.prompt_len - c,
            )
        else:
            logits, cache = self._prefill(self.params, prompt[None])
            self._write_slot(slot, cache, state.block_ids)
            if self.batcher.prefix_index is not None:
                self.metrics.counter("serve.prefix_misses").inc()
        return logits

    def _pick(self, slot: int, ids: np.ndarray, logits, row: int) -> int:
        """The slot's next token from row ``row`` of one program's
        logits: for a greedy request the id the device picked (``ids``,
        already on the host); for a sampled one its logits row, the only
        logits that cross, through ``sample_token``."""
        state = self.batcher.slots[slot]
        req = state.request
        if req.temperature <= 0:
            return int(ids[row])
        logits_row = np.asarray(logits[row])
        self.metrics.counter("serve.logits_rows_fetched").inc()
        key = self._keys[slot][len(state.generated)]
        tok = sample_token(
            logits_row[None],
            temperature=req.temperature,
            top_k=req.top_k,
            key=key,
        )
        return int(np.asarray(tok)[0])

    def _complete(self, state: SeqState) -> None:
        done = CompletedRequest(
            rid=state.rid,
            tokens=np.asarray(state.generated, np.int32),
            arrival_s=state.request.arrival_s,
            admitted_s=state.admitted_s,
            first_token_s=state.first_token_s,
            done_s=state.done_s,
            token_times=tuple(state.token_times),
        )
        self.completed[state.rid] = done
        if done.n_tokens > 1:
            self.metrics.histogram("serve.per_token_ms").observe(
                done.per_token_s * 1e3
            )
        record_event("serve_retire", rid=state.rid, n_tokens=done.n_tokens,
                     ttft_ms=round(done.ttft_s * 1e3, 3))

    def report(self) -> dict:
        """The replica's accounting: a VIEW over its metrics registry
        (one snapshot — counters, gauges, the TTFT and occupancy histograms)
        plus the loop counters the pool reads directly."""
        return {
            "steps": self.steps,
            "decode_steps": self.decode_steps,
            "completed": len(self.completed),
            **self._program_counts(),
            "block_len": self.block_len,
            "query_rows": self.bcfg.slots * self.block_len,
            "denoise_steps": (
                self.batcher.diffusion.steps if self.batcher.diffusion else 1
            ),
            **self.metrics.snapshot(),
        }

    # ---- warmup ------------------------------------------------------------

    def _zero_rows(self, lead: tuple) -> dict:
        """Zeros shaped ``(*lead, *row)`` for every part and layer of the
        pools: what a swap-in or an import scatters."""
        return jax.tree.map(
            lambda p: jnp.zeros((*lead, *p.shape[2:]), p.dtype), self.pools
        )

    def warmup(
        self, prompt_lens, block_counts=(), suffix_buckets=(),
        import_counts=(),
    ) -> None:
        """Compile the decode step, each distinct prompt length's prefill,
        the greedy pick over the logits of both, and each distinct
        reservation size's pool write before a timed run
        (compiles otherwise land inside the first requests' latency).
        ``block_counts``: the distinct ``pcfg.blocks_for(prompt + max_new)``
        values the workload will reserve.  Under on-demand admission the
        swap-in scatter is warmed for EVERY block count (a resume's count
        is ``length//bs + 1`` at whatever length eviction struck — one
        scatter compile per count, and an unwarmed one lands inside the
        preemption stall it is supposed to be ending).
        ``suffix_buckets``: ``(cached_len, suffix_len)`` pairs the
        prefix-cache workload will hit — suffix prefill compiles per
        distinct pair (the prefix shape carries the offset), and an
        unwarmed bucket puts its compile inside the very TTFT the cache
        hit was supposed to shrink.  Each bucket also warms the offset
        scatter for every remaining-block count it can need."""
        S, P = self.bcfg.slots, self.pcfg.blocks_per_seq
        # Every program is warmed on the engine's OWN pools and state,
        # donated and kept as the engine keeps them in a round (no second
        # pool is ever allocated: at 128 slots a linear-attention model's
        # pools and state are a third of the chip).  What the warm-up
        # writes is invisible: an all-inactive decode round and every pool
        # write land in the null block alone (block id 0, n times over:
        # the same compiled scatter as n real blocks), and the state write
        # puts slot 0's state back where it came from.
        def null(n):
            return np.zeros((n,), np.int32)

        # the round's programs on the arrays of no live slot, then on what
        # the first hands the round after it, as it lies on the device (a
        # round dispatched ahead); the greedy pick is warmed on both of its
        # shapes, a one-token round's (S, V) logits here and a prefill's
        # (1, V) below
        arrays, feed = self._kind.arrays([None] * S, P), None
        for _ in range(2):
            *_, feed = self._kind.dispatch(self, arrays, feed)
        jax.block_until_ready(feed)
        b = self.block_len
        cache = None
        # a diffusion block prefills a prompt's whole blocks only
        for t in sorted(set(int(t) // b * b for t in prompt_lens) - {0}):
            logits, cache = self._prefill(
                self.params, np.zeros((1, t), np.int32)
            )
            if logits is not None:
                jax.block_until_ready(self._greedy_ids(logits))
        if self.state:
            self.state = self._write_state(
                self.state, read_state(self.state, np.int32(0)), np.int32(0)
            )
        counts = set(int(n) for n in block_counts)
        if self.batcher.ondemand:
            # on-demand writes use block counts the caller's reservation
            # math never names: admission scatters blocks_for(prompt)
            # blocks and recompute-resume scatters length//bs + 1 — warm
            # the prefill write AND the swap-in scatter for every count,
            # or the compile lands inside the TTFT / preemption stall it
            # was supposed to end
            counts |= set(range(1, P + 1))
        bs = self.pcfg.block_size
        if counts and cache is None:
            _, cache = self._prefill(self.params, np.zeros((1, b), np.int32))
        for n in sorted(counts):
            self.pools = self._write(self.pools, cache, null(n))
            if self.batcher.ondemand:
                self.pools = self._write_back(
                    self.pools, self._zero_rows((n * bs,)), null(n)
                )
        # migrated-KV import scatter: one compile per inbound block
        # count — an unwarmed one stalls the decode replica's engine
        # loop mid-handoff, landing inside the very inter-token p99 the
        # disaggregation exists to protect
        for n in sorted(set(int(n) for n in import_counts)):
            self.pools = self._write_import(
                self.pools, self._zero_rows((n, bs)), null(n)
            )
        for c, s in sorted(set((int(c), int(s)) for c, s in suffix_buckets)):
            if c < 1 or s < 1:
                # c need NOT be block-aligned: the COW case caches
                # prompt_len - 2, which lands mid-block in the fork
                raise ValueError(
                    f"suffix bucket ({c}, {s}): cached_len and "
                    f"suffix_len must both be >= 1"
                )
            nc = -(-c // bs)  # chain blocks covering the cached prefix
            _, cache = self._hit_prefill(
                self.params, np.zeros((1, s), np.int32), self.pools,
                null(nc), c,
            )
            sb = c // bs
            for n in range(1, P - sb + 1):
                self.pools = self._write_at(self.pools, cache, null(n), sb)
        jax.block_until_ready((self.pools, self.state))
