"""Continuous-batching decode scheduler — ONE fixed-shape jitted step.

The request-at-a-time path (``CausalTransformerLM.generate``) traces
one executable per (batch, prompt-bucket, n_new) triple and a request
can only ride a batch formed at submit time. This scheduler instead
runs ONE jitted step over ``(max_slots,)`` rows against the paged KV
pool (``kv_pager.py``): every iteration it steps every active slot one
token, new sequences are admitted *into the running loop* by
prefilling into free pages (at the same power-of-two buckets
``generate()`` uses — ``zoo.gpt.prompt_bucket`` is shared so the two
can never drift), and finished sequences release their pages without
anything changing shape. Shapes never vary, so after
:meth:`DecodeScheduler.warmup` the PR 1 retrace sentry sees zero new
traces no matter how traffic arrives (the low-latency JIT-graph-capture
decode contract, PAPERS.md: arxiv 2604.23467).

Every program here is the ONE inference block and stack loop of
``nn/decoder_infer.py`` — the same ``generate()`` runs — over a cache
object the pager builds for the program's pool
(``kv_pager``: ``KVPager.rows``, ``.cache.chunk``): what a
layer's rows write and read is the whole difference between the dense
path and this one, and the pool's layout is the pager's alone. The
single-token step's attention is ``ops.paged_decode_attention``: on
the TPU a kernel that reads each slot's KV pages in place, up to the
slot's length; elsewhere (and for every multi-row query) the plain
``_reference_paged_attention``, whose math is the dense cache
object's value for value (same ``quant_kv`` codes/scales, same scale
factoring out of the einsums, same ``-1e9`` mask): padded/trash
positions contribute exact zeros after softmax, so paged greedy
decode is TOKEN-IDENTICAL to dense ``generate()`` — the
pager-correctness fence in ``tests/test_serving.py`` asserts it for
both the float and the int8-KV cache paths.

Two opt-in multipliers ride the same machinery (PR 16). With
``spec_k > 1`` each iteration drafts k-1 tokens on the host (prompt
lookup over the slot's own history — no second model), verifies all k
in ONE fixed-shape step whose per-row positions/masks generalize the
single-token step, and emits the agreeing prefix: because an accepted
row's cache context is exactly the sequential path's, greedy spec
output is token-identical to dense ``generate()`` by construction.
With ``prefix_sharing=True`` admission consults the pager's
content-addressed page-chain index: a prompt whose prefix already
sits in live pages ADOPTS them (refcount++), prefill runs only on the
novel suffix, and any write to a page with refcount > 1 first clones
it (copy-on-write) so siblings never observe the writer.

What a sequence's cached context is MADE of (KV pages, a state page,
latent rows, a hybrid's or a windowed decoder's two kinds) is the
pager's: the class ``KVPager.cache`` is the kind of page, and it says
which options it refuses, how a prompt is admitted (one bucket, or
chunks of one program), what a bucket prefill keeps, what a step
reads and the guards that hold for it alone (``kv_pager._Rows``).
Where a model's feed-forward routes (``ops/moe.py``) the step hands
the held experts' pair counts back with the tokens, in the one read.

The loop keeps ONE decode step in flight: :meth:`DecodeScheduler.step`
launches step n+1 from step n's device-resident outputs and only then
reads step n's tokens, so neither the read-back nor the next launch
lies between two steps on the device. The host's mirror therefore runs
one step behind the device; :meth:`DecodeScheduler.drain` brings it
level, and everything that needs it level (an admission, the
gateway's pause, park and fault paths) drains or drops first. The
speculative step drafts from the tokens it has just read and
copy-on-write looks at lengths the host must hold, so ``spec_k > 1``
and ``prefix_sharing`` read each step before the next.

The scheduler is single-threaded host logic (the gateway's worker
drives it); requests are duck-typed: ``.prompt`` (1-D int32),
``.max_new``, ``.temperature``, ``.eos_id``, and ``push(tok)`` /
``finish()`` / ``fail(exc)`` callbacks (``gateway.TokenStream``).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.serving.kv_pager import KVPager, PageTableError
from deeplearning4j_tpu.zoo.gpt import prompt_bucket

#: every ``_build_*`` jitted entry point in this module must have an
#: entry here describing its warmup feed, and :meth:`warmup` must
#: iterate the table — ``tools/lint_instrumentation.py`` rule 7 keeps
#: the builder set and this table in lockstep (the PR 5 WARMUP_FEEDS
#: contract: an unfed builder cold-traces on the first live request)
WARMUP_FEEDS = {
    "_build_step_fn":
        "(params, pool, page_table[S,MP]i32, lengths[S]i32, "
        "active[S]bool, prev[S]i32, temps[S]f32, top_p f32, ctr i32) "
        "— one signature total, warmed once",
    "_build_admit_fn":
        "(params, pool, page_ids[tb/block]i32, prompt[1,tb]i32, "
        "t0 i32, temp f32, top_p f32, ctr i32) — one signature per "
        "power-of-two prompt bucket (prompt_bucket), each warmed",
    "_build_chunk_admit_fn":
        "(params, pool, carried, where, tokens[1,chunk]i32, "
        "start i32, t0 i32, temp f32, top_p f32, ctr i32) — a "
        "retention or hybrid model's prefill: one signature total "
        "(prefill_chunk rows; carried and where as the pager's chunk "
        "class gives them), warmed once in place of the buckets",
    "_build_spec_step_fn":
        "(params, pool, page_table[S,MP]i32, lengths[S]i32, "
        "active[S]bool, prev[S]i32, drafts[S,k-1]i32) — one "
        "signature per k in SPEC_KS (the k grid); the configured k "
        "is warmed",
    "_build_suffix_admit_fn":
        "(params, pool, page_row[MP]i32, suffix[1,sb]i32, start i32, "
        "t0 i32, temp f32, top_p f32, ctr i32) — one signature per "
        "power-of-two SUFFIX bucket; warmup covers the downward "
        "closure of the reachable prompt buckets (a shared prefix "
        "can leave any shorter suffix)",
    "_build_cow_fn":
        "(pool, src i32, dst i32) — one signature total, warmed once",
}

#: the speculative-decode k grid: ``spec_k`` must come from this tuple
#: so :meth:`DecodeScheduler.warmup` AOT-captures the verify step the
#: live path will run — lint rule 10 holds this constant, the
#: ``_build_spec_step_fn`` WARMUP_FEEDS entry and the warmup() body in
#: lockstep (an off-grid k would cold-trace on the first spec step)
SPEC_KS = (2, 4, 8)


class _Slot:
    """Host state of one occupied decode slot."""

    __slots__ = ("req", "length", "remaining", "history")

    def __init__(self, req, length: int, remaining: int,
                 history: Optional[list] = None):
        self.req = req
        self.length = length        # cache positions written so far
        self.remaining = remaining  # tokens still to generate
        # prompt + emitted tokens, host-side: the prompt-lookup draft
        # source for speculative decode (no second model needed)
        self.history = history if history is not None else []


class _InFlight:
    """A decode step launched and not yet read: its tokens on the
    device, the slots that took part with the ``_Slot`` each held at
    launch (a token goes to that request and to no other), and the
    launching iteration's first stamp."""

    __slots__ = ("nxt", "slots", "t0", "pairs")

    def __init__(self, nxt, slots, t0: float, pairs=None):
        self.nxt = nxt
        self.slots = slots
        self.t0 = t0
        #: an expert model's held experts' pair counts
        #: [expert layers, n_held], on the device; else None
        self.pairs = pairs


class DecodeScheduler:
    """In-flight batched decode over a shared paged KV pool.

    ``max_context`` bounds prompt+generation per sequence (must be a
    multiple of ``block`` and at most ``model.max_len``); ``n_pages``
    sizes the pool (default: enough for every slot at full context —
    pass less to exercise admission control). Sampling config is
    gateway-level and static (``sample``/``top_k``/``top_p`` are trace
    keys exactly as in ``generate()``); per-request ``temperature``
    rides as a traced [S] vector so it never retraces.
    """

    def __init__(self, model, net, *, max_slots: int = 8,
                 block: int = 16, n_pages: Optional[int] = None,
                 max_context: Optional[int] = None,
                 sample: bool = False, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0,
                 spec_k: int = 1, prefix_sharing: bool = False):
        import jax.numpy as jnp

        self.model = model
        self.net = net
        self.max_slots = int(max_slots)
        self.block = int(block)
        mc = int(max_context or model.max_len)
        if mc > model.max_len:
            raise ValueError(f"max_context={mc} exceeds model "
                             f"max_len={model.max_len}")
        if mc % self.block:
            raise ValueError(f"max_context={mc} must be a multiple of "
                             f"block={self.block} so pages tile every "
                             "prompt bucket exactly")
        if min(16, mc) % self.block:
            raise ValueError(f"block={self.block} must divide the "
                             "smallest prompt bucket (16)")
        self.max_context = mc
        self.sample = bool(sample)
        self.top_k = top_k
        self.top_p = top_p
        self.seed = int(seed)
        self.spec_k = int(spec_k)
        if self.spec_k != 1:
            if self.spec_k not in SPEC_KS:
                raise ValueError(
                    f"spec_k={spec_k} not in SPEC_KS={SPEC_KS} — "
                    "warmup only pre-captures the k grid, an off-grid "
                    "k would cold-trace on the first live step")
            if self.sample:
                raise ValueError(
                    "speculative decode is greedy-only: the accept "
                    "rule compares per-row argmax against the draft; "
                    "under sampling it would skew the distribution")
        self.prefix_sharing = bool(prefix_sharing)
        kind, _ = KVPager.kind_of(model)
        for name, on in (("prefix_sharing", self.prefix_sharing),
                         ("spec_k", self.spec_k != 1),
                         ("cache_quant", bool(model.cache_quant))):
            if on and name in kind.refuses:
                raise ValueError(
                    f"{name} with {kind.serves}: {kind.refuses[name]}")
        self.pager = KVPager.for_model(model, self.max_slots, self.block,
                                       n_pages, mc)
        #: entries of a sequence's row of the page table
        self.max_pages_per_seq = self.pager.pages_for(mc)
        #: the whole pool is recurrent state: one page a sequence
        self.recurrent = kind.state == slice(None)
        #: rows of the ONE prefill program the kind admits by (``None``:
        #: the power-of-two buckets), and what the chunks of the prompt
        #: being admitted hand on beside the pool
        self.prefill_chunk, self._prefill_hist = kind.chunks(model, mc)
        #: expert layers of the model (their counts come back with
        #: every step's tokens)
        experts = getattr(model, "experts", None)
        self.expert_layers = (0 if experts is None
                              else model.n_layers - experts.first_dense)
        # per-slot host state, mirrored into the small int arrays the
        # fixed-shape step consumes each iteration
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        self._page_table = np.zeros(
            (self.max_slots, self.max_pages_per_seq), np.int32)
        self._lengths = np.zeros(self.max_slots, np.int32)
        self._prev = np.zeros(self.max_slots, np.int32)
        self._temps = np.ones(self.max_slots, np.float32)
        # device-side feed cache: in steady state the step feeds back
        # its own outputs (prev=nxt, lengths carried in-program) and
        # the static arrays stay resident — zero h2d per token; an
        # admission marks the feed dirty for a one-shot rebuild, any
        # other change of membership sends a new ``active`` mask alone
        self._dev_feed: Optional[dict] = None
        self._feed_dirty = True
        self._fed_act: Optional[list] = None    # slots of that mask
        #: the step launched and not yet read (None: the mirror is
        #: level with the device)
        self._inflight: Optional[_InFlight] = None
        #: whether a step may be launched before its predecessor's
        #: tokens are read: not where the next launch needs them on
        #: the host (``_step_spec`` drafts from them, ``_cow_writable``
        #: reads the mirror's lengths)
        self._run_ahead = self.spec_k == 1 and not self.prefix_sharing
        self._t_read = 0.0          # when the last step's read returned
        self._ctr = 0               # rng fold counter (step + admit)
        # admission-path scalar constants, uploaded once: top_p never
        # changes per request and temp defaults to 1.0 — re-wrapping
        # them per admit is pure fixed overhead on the TTFT path
        self._topp_dev = jnp.asarray(
            1.0 if self.top_p is None else self.top_p, jnp.float32)
        self._temp_one = jnp.asarray(1.0, jnp.float32)
        self.steps = 0
        self.tokens_out = 0
        #: id of the caller's iteration (the gateway loop's), stamped
        #: on the records made inside it
        self.cause = None
        self._step_fn = self._build_step_fn()
        self._chunk_fn = (self._build_chunk_admit_fn()
                          if self.prefill_chunk else None)
        self._admit_fns: Dict[int, object] = {}
        self._spec_fn = (self._build_spec_step_fn(self.spec_k)
                         if self.spec_k > 1 else None)
        self._suffix_fns: Dict[int, object] = {}
        self._cow_fn = (self._build_cow_fn()
                        if self.prefix_sharing else None)

    # -- jitted entry points (lint rule 7: sentry.jit, WARMUP_FEEDS) -----
    def _identity(self, **program):
        """What one of this scheduler's traced programs depends on
        beside its arguments, as ``sentry.jit(identity=...)`` takes
        it: a warm start then loads the program by a key that needs no
        trace (``perf/aot_store.py``). The model and the pager are
        said attribute by attribute (their specs, sizes and dtypes, the
        pager's cache class by name; of the model all but what only
        training reads: its init seed and its updater), the scheduler
        by every constructor argument a builder closes over and what
        ``__init__`` derives from them; ``program`` is the builder's
        own (a bucket, a draft width). A model of a class from outside
        the package cannot be said (``aot_store.CannotSay``), and the
        programs are traced."""
        from deeplearning4j_tpu.perf import aot_store
        own = {k: getattr(self, k) for k in (
            "max_slots", "block", "max_context", "max_pages_per_seq",
            "prefill_chunk", "spec_k", "sample", "top_k", "top_p",
            "seed", "prefix_sharing", "expert_layers")}
        dims = {k: v for k, v in vars(self.model).items()
                if not k.startswith("_") and k not in ("seed", "updater")}
        return aot_store.describe({
            "model": type(self.model), "dims": dims,
            "pager": self.pager,
            "pool": [(a.shape, a.dtype) for a in self.pager.pool],
            "scheduler": own, "program": program})

    def _first_token(self, params, row, scope, temp, top_p, ctr):
        """A prefill's TTFT token from the prompt's last row ``[1, F]``:
        the head, then the pick rule of the decode step under the
        admission's own key."""
        import jax

        logits0 = di.logits(params, row, self.model, scope)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), ctr)
        _, sub = jax.random.split(key)
        return di.pick(logits0, temp, top_p, sub, sample=self.sample,
                       top_k=self.top_k,
                       nucleus=self.top_p is not None)

    def _build_step_fn(self):
        """One decode iteration for every slot: token ids [S] -> next
        token ids [S], pool updated in place (each slot writes its
        position's KV into its own page, or rewrites its state page;
        inactive slots write nothing live). Fixed shapes throughout:
        THE serving hot path."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.perf import sentry

        model = self.model
        block_scope = self.pager.cache.scope

        # pool is threaded through and returned so the caller rebinds
        # the pager's arrays (donation-friendly on accelerators)
        def step(params, pool, page_table, lengths, active, prev,
                 temps, top_p, ctr):
            cache = self.pager.rows(model, pool, page_table,
                                    lengths[:, None], active[:, None])
            pairs = [] if self.expert_layers else None
            x = di.stack(params, prev, model, cache.attend,
                         "paged_decode", block_scope, counts=pairs,
                         live=active)
            logits = di.logits(params, x, model, "paged_decode")
            key = jax.random.fold_in(
                jax.random.PRNGKey(self.seed), ctr)
            nxt = di.pick(
                logits, temps[:, None], top_p, key, sample=self.sample,
                top_k=self.top_k, nucleus=self.top_p is not None)
            nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
            # carry lengths forward ON DEVICE: steady-state steps feed
            # back (nxt, lengths+active) without any host->device
            # upload — only admissions/retirements dirty the feed
            out = (nxt, cache.pool,
                   lengths + active.astype(lengths.dtype))
            # an expert model's step also says what its held experts
            # computed: [expert layers, n_held] pairs, read with nxt
            return out + (jnp.stack(pairs),) if pairs else out

        # pool is donated: the caller always rebinds the returned pool
        # (scheduler invariant), so XLA may alias in/out and the step
        # writes pages in place — without this, every call on a
        # donation-capable backend copies the whole multi-MB pool
        return sentry.jit(step, name="serving.decode_step",
                          identity=self._identity, donate_argnums=(1,))

    def _build_spec_step_fn(self, k: int):
        """Speculative verify step: score ``prev`` plus the k-1 host
        drafts in ONE fixed-shape forward ([S, k] rows at positions
        lengths..lengths+k-1), take the per-row greedy argmax, accept
        the agreeing prefix. Because row r's cache context is exactly
        the sequential path's whenever drafts 1..r matched, every
        accepted token is the token single-step decode would have
        produced — the identity fence holds by construction, the step
        just emits 1..k of them per slot. Rejected rows leave stale KV
        at positions length+e..length+k-1; the NEXT step's k writes
        start at length+e and e >= 1, so the garbage is overwritten
        before any mask can see it (the in-program rollback)."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.perf import sentry

        model = self.model
        S = self.max_slots

        def step(params, pool, page_table, lengths, active, prev,
                 drafts):
            toks = jnp.concatenate([prev[:, None], drafts], axis=1)
            pos = (lengths[:, None]
                   + jnp.arange(k, dtype=lengths.dtype)[None, :])
            cache = self.pager.rows(model, pool, page_table, pos,
                                    active[:, None])
            x = di.stack(params, toks.reshape(-1), model, cache.attend,
                         "spec_decode")
            logits = di.logits(params, x, model,
                               "spec_decode").reshape(S, k, -1)
            # per-row greedy pick — same argmax `pick(sample=False)`
            # runs, just vectorized over the k rows
            m = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            agree = (m[:, :-1] == drafts).astype(jnp.int32)
            e = 1 + jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
            e = jnp.where(active, e, 0)
            m = jnp.where(active[:, None], m, 0)
            prev_next = jnp.take_along_axis(
                m, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
            # lengths advance by the ACCEPTED count in-program — the
            # steady-state feedback loop needs no host upload beyond
            # the k-1 draft ints per slot
            return m, e, cache.pool, lengths + e, prev_next

        return sentry.jit(step, name=f"serving.spec_step_k{k}",
                          identity=lambda: self._identity(k=k),
                          donate_argnums=(1,))

    def _build_admit_fn(self, tb: int):
        """Prefill-into-pages for prompt bucket ``tb``: ONE batched
        causal forward over the padded prompt (flash dispatch, the
        head on one row: the forward of the dense ``generate()``
        prefill), each layer's keys and values kept as this
        sequence's pages, first generated token returned. One
        executable per power-of-two bucket, exactly the ``generate()``
        compile set."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.perf import sentry

        model = self.model

        def admit(params, pool, page_ids, prompt_pad, t0, temp, top_p,
                  ctr):
            kv = []
            attend = self.pager.cache.prefill(
                model, lambda li, *kept: kv.append(kept), t0[None])
            pairs = [] if self.expert_layers else None
            # the experts route the prompt's rows, not the bucket's
            # padding, and the flash kernel stops at the prompt's end
            x = di.stack(params, prompt_pad, model, attend, "prefill",
                         counts=pairs,
                         live=jnp.arange(prompt_pad.shape[1])[None] < t0)
            row = jax.lax.dynamic_index_in_dim(x, t0 - 1, axis=1,
                                               keepdims=False)
            out = (self.pager.cache.write_prompt(model, pool, page_ids, kv),
                   self._first_token(params, row, "prefill", temp,
                                     top_p, ctr))
            return out + (sum(jnp.sum(p) for p in pairs),) if pairs \
                else out
        return sentry.jit(admit, name="serving.prefill",
                          identity=lambda: self._identity(bucket=tb),
                          donate_argnums=(1,))

    def _build_chunk_admit_fn(self):
        """The prefill of a kind that admits by chunks: ONE program of
        ``prefill_chunk`` rows, run ``ceil(t0 / chunk)`` times for a
        prompt of ``t0`` tokens, each call against what the calls
        before left (the chunk class of the pager's cache,
        ``KVPager.cache.chunk``; ``where`` is where that class finds
        the sequence, ``carried`` what its chunks hand on beside the
        pool). After the last call the pool holds the sequence after
        position ``t0 - 1`` exactly: rows at and past ``t0`` are masked
        out of it. The head runs only in the call that holds row
        ``t0 - 1``; the others return token 0."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.perf import sentry

        model = self.model
        chunk = self.prefill_chunk

        def admit(params, pool, carried, where, toks, start, t0, temp,
                  top_p, ctr):
            valid = (start + jnp.arange(chunk, dtype=jnp.int32)
                     < t0)[None, :]
            cache = self.pager.cache.chunk(model, pool, carried, where,
                                           start, valid)
            x = di.stack(params, toks, model, cache.attend,
                         "chunk_prefill")               # [1, C, F]

            def first_token(x):
                row = jax.lax.dynamic_slice_in_dim(
                    x[0], t0 - 1 - start, 1, axis=0)
                return self._first_token(params, row, "chunk_prefill",
                                         temp, top_p, ctr)

            g0 = jax.lax.cond(t0 <= start + chunk, first_token,
                              lambda x: jnp.zeros((1,), jnp.int32), x)
            return cache.pool, cache.carried, g0

        return sentry.jit(admit, name="serving.prefill",
                          identity=lambda: self._identity(chunk=chunk),
                          donate_argnums=(1, 2))

    def _chunk_where_shapes(self):
        """Shapes of the chunk program's ``where``, for lowering."""
        import jax
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.pager.cache.chunk.where(
                0, [0], np.zeros(self.max_pages_per_seq, np.int32)))

    def _admit_fn(self, tb: int):
        fn = self._admit_fns.get(tb)
        if fn is None:
            fn = self._admit_fns[tb] = self._build_admit_fn(tb)
        return fn

    def _build_suffix_admit_fn(self, sb: int):
        """Prefill ONLY the novel suffix of a shared-prefix admission:
        the first ``start`` positions already sit in adopted pages, so
        the forward runs the ``sb``-bucketed suffix rows as the rows
        of ONE slot (``KVPager.rows``, S=1) — they attend the shared
        pages through the slot's page table and write their own KV
        into the novel (or copy-on-write) pages. Admission cost scales
        with the SUFFIX, not the prompt (PAPERS.md: arxiv 2603.09555's
        O(1) shared-prefix caching contract). Logits are read at
        prompt row ``t0-1-start`` and picked by the dense admit's own
        rule."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.perf import sentry

        model = self.model

        def admit(params, pool, page_row, suffix_pad, start, t0, temp,
                  top_p, ctr):
            pos = (start
                   + jnp.arange(sb, dtype=jnp.int32))[None, :]
            act = jnp.arange(sb, dtype=jnp.int32)[None, :] < (t0
                                                              - start)
            cache = self.pager.rows(model, pool, page_row[None, :], pos,
                                    act)
            x = di.stack(params, suffix_pad.reshape(-1), model,
                         cache.attend, "suffix_prefill")
            row = jax.lax.dynamic_slice_in_dim(
                x, t0 - 1 - start, 1, axis=0)
            return cache.pool, self._first_token(
                params, row, "suffix_prefill", temp, top_p, ctr)

        return sentry.jit(admit, name="serving.suffix_prefill",
                          identity=lambda: self._identity(suffix=sb),
                          donate_argnums=(1,))

    def _build_cow_fn(self):
        """Copy one physical page (all layers, codes AND scales) —
        the copy-on-write primitive: a writer holding a page whose
        refcount exceeds one clones it before its next KV write so
        sibling readers keep the original bytes."""
        from deeplearning4j_tpu.perf import sentry

        # donated: the clone is an in-place one-page write on a
        # donation-capable backend rather than a whole-pool copy —
        # this keeps shared admissions O(suffix), not O(pool)
        return sentry.jit(self.pager.copy_page, name="serving.cow_copy",
                          identity=self._identity, donate_argnums=(0,))

    def _suffix_fn(self, sb: int):
        fn = self._suffix_fns.get(sb)
        if fn is None:
            fn = self._suffix_fns[sb] = self._build_suffix_admit_fn(sb)
        return fn

    # -- host-side scheduling -------------------------------------------
    def pages_needed(self, t0: int, max_new: int) -> int:
        """Pages a (prompt, budget) pair needs for its WHOLE life:
        the prefilled bucket plus every decode write (positions
        ``t0 .. t0+max_new-2``) — reserved up front so an admitted
        sequence can never stall mid-flight on an empty free list."""
        tb = (0 if self.prefill_chunk     # padding rows write no page
              else prompt_bucket(t0, self.max_context))
        return self.pager.pages_for(max(tb, t0 + max_new - 1))

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def can_admit(self, t0: int, max_new: int) -> bool:
        return (self.free_slot() is not None
                and self.pages_needed(t0, max_new)
                <= self.pager.free_pages())

    def active_count(self) -> int:
        return sum(s is not None for s in self._slots)

    def admit(self, req) -> bool:
        """Prefill ``req`` into free pages and occupy a slot; emits the
        first generated token (the TTFT token). Returns False when
        capacity is lacking — the caller keeps it queued. The step in
        flight is drained first: the slot and the pages are chosen,
        and the feed rebuilt, from a mirror that is level with the
        device (the prefill's own read would wait that step out
        anyway, with its tokens undelivered)."""
        import jax.numpy as jnp

        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        t0, max_new = prompt.shape[0], int(req.max_new)
        self.drain()
        slot = self.free_slot()
        if slot is None:
            return False
        if self.prefix_sharing:
            match = self.pager.match_prefix(prompt)
            if match is not None:
                return self._admit_shared(req, slot, prompt, t0,
                                          max_new, match)
        # a model with a recurrent state runs its prompt as chunks of
        # one program; one with none as ONE bucket
        chunked = self._chunk_fn is not None
        tb = (self.prefill_chunk if chunked
              else prompt_bucket(t0, self.max_context))
        n_chunks = -(-t0 // tb) if chunked else 1
        # resolve (possibly build) the bucket executable BEFORE taking
        # pages: everything after the reservation is under the
        # release-on-failure try below
        fn = self._chunk_fn if chunked else self._admit_fn(tb)
        pages = self.pager.alloc(self.pages_needed(t0, max_new), req)
        if pages is None:
            return False
        ts0 = obs.now()
        row = self._page_table[slot]
        row[:] = 0
        row[:len(pages)] = pages
        pad = np.zeros((1, n_chunks * tb), np.int32)
        pad[0, :t0] = prompt
        self._ctr += 1
        # `is not None`, never truthiness (the falsy-deadline lesson):
        # the gateway rejects temperature <= 0 at submit
        temp = getattr(req, "temperature", None)
        ts1 = obs.now()
        try:
            params = self.model.decode_params(self.net)
            tail = (jnp.asarray(t0, jnp.int32),
                    (self._temp_one if temp is None
                     else jnp.asarray(temp, jnp.float32)),
                    self._topp_dev, jnp.asarray(self._ctr, jnp.int32))
            flash = {}      # a bucket's attention, in (query, key) pairs
            if chunked:
                where = self.pager.cache.chunk.where(slot, pages, row)
                for c in range(n_chunks):
                    pool, self._prefill_hist, g0 = fn(
                        params, self.pager.pool, self._prefill_hist,
                        where, jnp.asarray(pad[:, c * tb:(c + 1) * tb]),
                        jnp.asarray(c * tb, jnp.int32), *tail)
                    self.pager.pool = pool
            else:
                pool, g0, *pairs = fn(
                    params, self.pager.pool,
                    self.pager.prompt_pages(slot, pages, tb, t0),
                    jnp.asarray(pad), *tail)
                self.pager.pool = pool
                # (counted while the device runs the admission)
                flash = self.pager.cache.prefill_pairs(self.model, tb, t0)
            ts2 = obs.now()
            first = int(np.asarray(g0)[0])  # blocking device sync
            pairs = int(np.asarray(pairs[0])) if (
                not chunked and pairs) else 0
        except BaseException:
            # a failed prefill must not leak the reservation (the
            # slot was never occupied; its table row resets)
            self._page_table[slot] = 0
            self._feed_dirty = True
            self.pager.release(req)
            raise
        ts3 = obs.now()
        obs.record_step("serving.prefill", ts0, ts1, ts2, ts3,
                        args={"bucket": tb, "t0": t0, "slot": slot,
                              "chunks": n_chunks,
                              **({"expert_pairs": pairs}
                                 if self.expert_layers else {}),
                              **flash,
                              "rid": getattr(req, "rid", None)},
                        cause=self.cause)
        obs.metrics.SERVING_PREFILL.observe(ts3 - ts0)
        obs.metrics.SERVING_EXPERT_PAIRS.inc(pairs)
        if self.prefix_sharing:
            # publish this prompt's page chain so later admissions
            # with the same prefix can adopt the pages instead of
            # re-prefilling them
            self.pager.register_chain(prompt, pages)
        self._occupy(slot, req, t0, max_new, first, temp, prompt)
        return True

    def _admit_shared(self, req, slot, prompt, t0: int, max_new: int,
                      match) -> bool:
        """Admit ``req`` by ADOPTING a matched prefix chain: incref
        the shared pages, allocate only the novel remainder of the
        whole-life reservation, and prefill just the suffix. A
        whole-prompt (tail-key) match copy-on-writes the final shared
        page first — position ``t0-1`` must be recomputed there to
        recover the first-token logits, and that write may not touch
        a page siblings still read."""
        import jax.numpy as jnp

        shared_len, spages, tail = match
        total = self.pages_needed(t0, max_new)
        novel = total - len(spages) + (1 if tail else 0)
        suffix = t0 - shared_len
        sb = prompt_bucket(suffix, self.max_context)
        # resolve (possibly build) the suffix executable BEFORE taking
        # pages — same discipline as the dense path
        fn = self._suffix_fn(sb)
        new_pages = self.pager.alloc(novel, req)
        if new_pages is None:
            return False
        ts0 = obs.now()
        try:
            self.pager.adopt(spages, req)
        except BaseException:
            self.pager.release(req)
            raise
        try:
            if tail:
                old_tail = spages[-1]
                target = new_pages[0]
                self.pager.pool = self._cow_fn(
                    self.pager.pool, jnp.asarray(old_tail, jnp.int32),
                    jnp.asarray(target, jnp.int32))
                self.pager.drop_ref(req, old_tail)
                obs.metrics.SERVING_PREFIX_COW.inc()
                row_pages = list(spages[:-1]) + [target] \
                    + list(new_pages[1:])
            else:
                row_pages = list(spages) + list(new_pages)
            row = self._page_table[slot]
            row[:] = 0
            row[:len(row_pages)] = row_pages
            pad = np.zeros((1, sb), np.int32)
            pad[0, :suffix] = prompt[shared_len:]
            self._ctr += 1
            temp = getattr(req, "temperature", None)
            ts1 = obs.now()
            pool, g0 = fn(
                self.model.decode_params(self.net), self.pager.pool,
                jnp.asarray(np.asarray(row, np.int32)),
                jnp.asarray(pad), jnp.asarray(shared_len, jnp.int32),
                jnp.asarray(t0, jnp.int32),
                (self._temp_one if temp is None
                 else jnp.asarray(temp, jnp.float32)),
                self._topp_dev,
                jnp.asarray(self._ctr, jnp.int32))
            self.pager.pool = pool
            ts2 = obs.now()
            first = int(np.asarray(g0)[0])  # blocking device sync
        except BaseException:
            # one release drops BOTH the adopted refs and the novel
            # pages — shared pages survive for their siblings
            self._page_table[slot] = 0
            self._feed_dirty = True
            self.pager.release(req)
            raise
        ts3 = obs.now()
        obs.record_step("serving.prefill", ts0, ts1, ts2, ts3,
                        args={"bucket": sb, "t0": t0, "slot": slot,
                              "shared": shared_len,
                              "rid": getattr(req, "rid", None)},
                        cause=self.cause)
        obs.metrics.SERVING_PREFILL.observe(ts3 - ts0)
        obs.metrics.SERVING_PREFIX_HITS.inc()
        obs.metrics.SERVING_PREFIX_SAVED.inc(shared_len)
        self.pager.register_chain(prompt, row_pages)
        self._occupy(slot, req, t0, max_new, first, temp, prompt)
        return True

    def _occupy(self, slot: int, req, t0: int, max_new: int,
                first: int, temp, prompt) -> None:
        """Post-prefill slot bookkeeping shared by the dense and the
        shared-prefix admission paths: mirror state, emit the TTFT
        token, retire immediately if the budget was one token."""
        self._slots[slot] = _Slot(req, length=t0,
                                  remaining=max_new - 1,
                                  history=list(map(int, prompt))
                                  + [first])
        self._lengths[slot] = t0
        self._prev[slot] = first
        self._temps[slot] = 1.0 if temp is None else temp
        self._feed_dirty = True
        obs.metrics.SERVING_SLOTS.set(self.active_count())
        req.push(first)
        obs.metrics.SERVING_TOKENS.inc()
        self.tokens_out += 1
        if self._slots[slot].remaining <= 0 or first == getattr(
                req, "eos_id", None):
            self._retire(slot)

    def _ensure_feed(self, act) -> dict:
        """The step's device-side feed for the slots ``act``. ``prev``
        and ``lengths`` are the last launched step's own outputs (the
        zero-h2d steady state) until an admission dirties the feed:
        then all of it is rebuilt from the host's mirror, which is
        level with the device because :meth:`admit` drained first.
        The ``active`` mask is the host's alone and goes up whenever
        the stepping slots changed (a retirement, an eviction, a
        budget that ends with the step in flight): a masked-out slot
        writes to the trash page and walks none, so nothing else of
        its feed is read."""
        import jax.numpy as jnp

        if self._feed_dirty or self._dev_feed is None:
            assert self._inflight is None, \
                "feed rebuilt from a mirror one step behind the device"
            self._check_feed()
            # copies: on a CPU backend ``jnp.asarray`` may ALIAS an
            # aligned host array, and the mirror is written (a
            # retirement zeroes its page-table row, ``_collect`` moves
            # lengths and prev on) while the step launched from this
            # feed may not have read it yet
            self._dev_feed = {
                "pt": jnp.asarray(self._page_table.copy()),
                "lengths": jnp.asarray(self._lengths.copy()),
                "prev": jnp.asarray(self._prev.copy()),
                "temps": jnp.asarray(self._temps.copy()),
                "top_p": jnp.asarray(
                    1.0 if self.top_p is None else self.top_p,
                    jnp.float32),
            }
            self._feed_dirty = False
            self._fed_act = None
        if act != self._fed_act:
            active = np.zeros(self.max_slots, bool)
            active[act] = True
            self._dev_feed["active"] = jnp.asarray(active)
            self._fed_act = act
        return self._dev_feed

    def _check_feed(self) -> None:
        """The host's guard of what the step is about to be handed,
        once a rebuilt feed (an admission, not a step) and before any
        device call: the page walks' kernels issue their copies with
        the compiler's bounds checks off (``ops/pallas_kernels.py``: a
        check of both addresses was two thirds of the scalar core's
        work a page), so a page number has to be the pager's own.
        Every entry of the page table lies under the pool's page
        count (the trash page 0 is one of them), and what the kind of
        page asks holds (``_Rows.check_feed``: a slot's row serves
        its last position; a ring is long enough). There is no
        switch. Raises :class:`kv_pager.PageTableError`."""
        pt, pager = self._page_table, self.pager
        bad = np.argwhere((pt < 0) | (pt >= pager.n_pages))
        if bad.size:
            s, e = map(int, bad[0])
            raise PageTableError(
                f"slot {s}'s page-table entry {e} is {int(pt[s, e])}: "
                f"the pool has pages 0..{pager.n_pages - 1}")
        pager.cache.check_feed(pager, self.model, pt, {
            i: int(self._lengths[i]) + max(slot.remaining, 0)
            for i, slot in enumerate(self._slots) if slot is not None})

    def step(self) -> int:
        """One continuous-batching iteration, one decode step in
        flight: launch the next step for every slot that goes on past
        the step in flight (a budget that ends with it is known now;
        an ``eos_id`` is not, and that slot's row in the step launched
        ahead is computed and discarded), THEN read the step in
        flight, deliver its tokens and retire what finished (their
        pages go back to the free list). The read returns while the
        step just launched runs. With nothing in flight the launch
        alone (the next call reads it); with nothing to launch the
        read alone (:meth:`drain`). Returns tokens delivered by this
        call (0 = idle, or a launch into an empty pipeline).
        ``prefix_sharing`` reads each step before the next launch;
        with ``spec_k > 1`` the iteration runs the speculative
        draft/verify/accept step instead and can emit up to k tokens
        per slot."""
        import jax.numpy as jnp

        prior = self._inflight
        # every occupied slot has a row in the step in flight (a slot
        # is only occupied by ``admit``, which drains first), so that
        # step takes one of each budget
        pending = int(prior is not None)
        act = [i for i, s in enumerate(self._slots) if s is not None
               and s.remaining > pending]
        if not act:
            return self.drain()
        if self.spec_k > 1:
            return self._step_spec(act)
        if self.prefix_sharing:
            # defense-in-depth: admission CoWs the tail eagerly, so a
            # live slot should never write a shared page — but if one
            # slipped through, clone it before the step can clobber it
            self._cow_writable(act, 1)
        ts0 = obs.now()
        self._ctr += 1
        f = self._ensure_feed(act)
        # what the step reads, from the host's mirror and what the
        # step in flight adds to it: no device read
        reads = self.pager.cache.step_reads(
            self.pager, self.model, self._lengths[act] + pending,
            self.max_pages_per_seq)
        ts1 = obs.now()
        nxt, pool, len_next, *pairs = self._step_fn(
            self.model.decode_params(self.net), self.pager.pool,
            f["pt"], f["lengths"], f["active"], f["prev"], f["temps"],
            f["top_p"], jnp.asarray(self._ctr, jnp.int32))
        self.pager.pool = pool
        # feed the step's own outputs back: no h2d on the clean path
        f["prev"], f["lengths"] = nxt, len_next
        self._inflight = _InFlight(
            nxt, [(i, self._slots[i]) for i in act], ts0,
            pairs[0] if pairs else None)
        ts2 = obs.now()
        # the blocking read: the predecessor's tokens, while the step
        # just launched runs; that step's own where the mode needs
        # them before its next launch
        due = prior if self._run_ahead else self._inflight
        n, ts3, experts = (self._collect(due) if due is not None
                           else (0, ts2, (0, 0, 0)))
        # ``deliver`` (ts3 → here) is the push/retire loop of the step
        # that was read; the expert counts are that step's too (the
        # host learns them with its tokens), the others the launched
        # step's
        args = {"active": len(act), **reads, "ahead": pending}
        if self.expert_layers:
            (args["expert_pairs"], args["experts_hit"],
             args["expert_pairs_max"]) = experts
        obs.record_step("serving.decode_step", ts0, ts1, ts2, ts3,
                        args=args, cause=self.cause, end=obs.now())
        obs.metrics.SERVING_AHEAD.inc(pending)
        return n

    def _collect(self, fl: _InFlight) -> tuple:
        """Read one launched step's tokens (the blocking device sync)
        and deliver them: each to the request that held its slot when
        the step was launched, and only if it still does (a slot that
        ended meanwhile, by ``eos_id``, eviction or shed, has its row
        discarded: never pushed, never counted); retire what
        finished. Observes ``SERVING_STEP`` once a device step with
        the wall time this step added: from the read before it, or
        from its own launch where that came later. Returns ``(tokens
        delivered, when the read returned, an expert model's (pairs
        its held experts computed, held experts hit, the fullest
        expert's pairs) over the expert layers)``."""
        if self._inflight is fl:
            self._inflight = None
        toks = np.asarray(fl.nxt)       # blocking device sync
        t_read = obs.now()
        experts = (0, 0, 0)
        if fl.pairs is not None:
            # computed by the same program: ready when the tokens are
            pairs = np.asarray(fl.pairs)
            experts = (int(pairs.sum()), int((pairs > 0).sum()),
                       int(pairs.max(axis=1).sum()))
            obs.metrics.SERVING_EXPERT_PAIRS.inc(experts[0])
        self.steps += 1
        n = 0
        for i, s in fl.slots:
            if self._slots[i] is not s:
                continue
            tok = int(toks[i])
            self._lengths[i] += 1
            self._prev[i] = tok
            s.length += 1
            s.remaining -= 1
            s.req.push(tok)
            n += 1
            if s.remaining <= 0 or tok == getattr(s.req, "eos_id",
                                                  None):
                self._retire(i)
        if not n:
            # every row discarded: no step or admission was launched
            # since (each needs a live slot or drains first), so the
            # next launch draws what it would have drawn had this one
            # never been made
            self._ctr -= 1
        obs.metrics.SERVING_STEP.observe(
            t_read - max(fl.t0, self._t_read))
        self._t_read = t_read
        obs.metrics.SERVING_TOKENS.inc(n)
        self.tokens_out += n
        return n, t_read, experts

    def drain(self) -> int:
        """Read the step in flight, if there is one, outside a launch:
        before an admission, a pause or a park, or when every live
        slot's budget ends with it. Afterwards the host's mirror holds
        every step launched. Returns tokens delivered."""
        fl = self._inflight
        if fl is None:
            return 0
        t0 = obs.now()
        n, *_ = self._collect(fl)
        obs.record("serving.drain", t0, obs.now(), self.cause, tokens=n)
        return n

    def _step_spec(self, act) -> int:
        """One speculative iteration: host-draft k-1 tokens per slot
        (prompt lookup over its token history — the one small h2d this
        mode pays per step, a documented deviation from the
        single-token path's zero-upload steady state), verify all k
        in one fixed-shape step, deliver the accepted prefix. Device
        lengths advance by the accepted count in-program; any slot
        that retires mid-acceptance (eos / budget) dirties the feed,
        so the rebuilt host mirror re-synchronizes the truncation."""
        import jax.numpy as jnp

        k = self.spec_k
        if self.prefix_sharing:
            self._cow_writable(act, k)
        ts0 = obs.now()
        self._ctr += 1
        f = self._ensure_feed(act)
        drafts_np = np.zeros((self.max_slots, k - 1), np.int32)
        for i in act:
            drafts_np[i] = self._draft(self._slots[i].history, k - 1)
        ts1 = obs.now()
        m, e, pool, len_next, prev_next = self._spec_fn(
            self.model.decode_params(self.net), self.pager.pool,
            f["pt"], f["lengths"], f["active"], f["prev"],
            jnp.asarray(drafts_np))
        self.pager.pool = pool
        f["prev"], f["lengths"] = prev_next, len_next
        ts2 = obs.now()
        toks = np.asarray(m)            # blocking device sync
        counts = np.asarray(e)
        ts3 = obs.now()
        self.steps += 1
        produced = 0
        for i in act:
            s = self._slots[i]
            n_acc = int(counts[i])
            pushed = 0
            retire = False
            for j in range(n_acc):
                tok = int(toks[i, j])
                s.req.push(tok)
                s.history.append(tok)
                pushed += 1
                s.remaining -= 1
                if s.remaining <= 0 or tok == getattr(
                        s.req, "eos_id", None):
                    retire = True
                    break
            s.length += pushed
            self._lengths[i] += pushed
            self._prev[i] = int(toks[i, pushed - 1])
            produced += pushed
            obs.metrics.SERVING_SPEC_DRAFTED.inc(k - 1)
            obs.metrics.SERVING_SPEC_ACCEPTED.inc(n_acc - 1)
            obs.metrics.SERVING_SPEC_ACCEPT.observe(
                (n_acc - 1) / (k - 1))
            if retire:
                self._retire(i)
        obs.record_step("serving.spec_step", ts0, ts1, ts2, ts3,
                        args={"active": len(act), "k": k,
                              "produced": produced}, cause=self.cause,
                        end=obs.now())
        obs.metrics.SERVING_STEP.observe(ts3 - ts0)
        obs.metrics.SERVING_TOKENS.inc(produced)
        self.tokens_out += produced
        return produced

    def _cow_writable(self, act, k: int) -> None:
        """Copy-on-write every page the next step's k writes could
        touch if its refcount exceeds one: clone the bytes, swap the
        clone into this slot's table row, decref the original —
        sibling readers keep the shared page untouched."""
        import jax.numpy as jnp

        for i in act:
            s = self._slots[i]
            length = int(self._lengths[i])
            lo = length // self.block
            hi = min((length + k - 1) // self.block,
                     self.max_pages_per_seq - 1)
            for pi in range(lo, hi + 1):
                pid = int(self._page_table[i, pi])
                if pid and self.pager.refcount(pid) > 1:
                    new = self.pager.cow(s.req, pid)
                    self.pager.pool = self._cow_fn(
                        self.pager.pool, jnp.asarray(pid, jnp.int32),
                        jnp.asarray(new, jnp.int32))
                    self._page_table[i, pi] = new
                    self._feed_dirty = True
                    obs.metrics.SERVING_PREFIX_COW.inc()

    def _draft(self, hist, n: int):
        """Prompt-lookup drafting: find the LATEST earlier occurrence
        of the trailing bigram (unigram fallback) in this slot's own
        history and propose its continuation; pad by repeating the
        last candidate. Free to compute, surprisingly accurate on
        repetitive continuations — and a wrong draft only costs the
        verify row it rode in."""
        L = len(hist)
        idx = None
        if L >= 2:
            a, b = hist[-2], hist[-1]
            for j in range(L - 3, -1, -1):
                if hist[j] == a and hist[j + 1] == b:
                    idx = j + 2
                    break
        if idx is None and L >= 1:
            a = hist[-1]
            for j in range(L - 2, -1, -1):
                if hist[j] == a:
                    idx = j + 1
                    break
        cand = list(hist[idx:idx + n]) if idx is not None else []
        last = cand[-1] if cand else (hist[-1] if hist else 0)
        while len(cand) < n:
            cand.append(last)
        return cand

    def _retire(self, slot: int) -> None:
        s = self._slots[slot]
        self._slots[slot] = None
        self._page_table[slot] = 0
        self.pager.release(s.req)
        obs.metrics.SERVING_SLOTS.set(self.active_count())
        s.req.finish()

    def shed_all(self, make_error) -> int:
        """Error out every in-flight sequence and release its pages —
        the fault path's guarantee: a poisoned step never leaves a
        wedged slot or a leaked page. ``make_error`` is a ZERO-ARG
        factory called once per stream: a shared exception instance
        would leak the first stream's tokens-so-far into every other
        client's structured error. The step in flight is dropped
        unread (the device may be what failed): no token of it
        reaches a failed stream."""
        n = 0
        self._inflight = None
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            self._slots[i] = None
            self._page_table[i] = 0
            self.pager.release(s.req)
            s.req.fail(make_error())
            n += 1
        obs.metrics.SERVING_SLOTS.set(0)
        return n

    def evict(self, req) -> bool:
        """Cancel one in-flight sequence (client went away): free its
        slot and pages without erroring the stream. Its row in a step
        already launched is discarded when that step is read."""
        for i, s in enumerate(self._slots):
            if s is not None and s.req is req:
                self._slots[i] = None
                self._page_table[i] = 0
                self.pager.release(req)
                obs.metrics.SERVING_SLOTS.set(self.active_count())
                req.finish()
                return True
        return False

    # -- AOT warmup ------------------------------------------------------
    def _step_feed_shapes(self) -> tuple:
        """The decode step's feed after ``(params, pool)``, as shapes
        (WARMUP_FEEDS["_build_step_fn"]): what :meth:`warmup` compiles
        from, and what ``chip_smoke.py`` and the AOT compile test
        lower the step from to read its kernels."""
        import jax
        import jax.numpy as jnp

        sds, i32 = jax.ShapeDtypeStruct, jnp.int32
        S, MP = self.max_slots, self.max_pages_per_seq
        return (sds((S, MP), i32), sds((S,), i32), sds((S,), jnp.bool_),
                sds((S,), i32), sds((S,), jnp.float32),
                sds((), jnp.float32), sds((), i32))

    def warmup(self, prompt_lens=None) -> Dict[str, float]:
        """AOT-compile the decode step (one signature) and the prefill
        executable of every reachable prompt bucket (a retention
        model's one chunk program in their place) BEFORE traffic —
        after this the sentry sees zero new traces from any admission
        order (the acceptance fence). Iterates :data:`WARMUP_FEEDS`'
        builder table so lint rule 7 can hold the two in lockstep."""
        import jax
        import jax.numpy as jnp

        assert set(WARMUP_FEEDS) == {"_build_step_fn",
                                     "_build_admit_fn",
                                     "_build_chunk_admit_fn",
                                     "_build_spec_step_fn",
                                     "_build_suffix_admit_fn",
                                     "_build_cow_fn"}
        if prompt_lens is None:
            prompt_lens = range(1, self.max_context)
        params = self.model.decode_params(self.net)
        pool_sds = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in self.pager.pool)
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        S, MP = self.max_slots, self.max_pages_per_seq
        seconds = self._step_fn.warmup(params, pool_sds,
                                       *self._step_feed_shapes())
        compiled = seconds > 0
        scalars = (sds((), i32), sds((), jnp.float32),
                   sds((), jnp.float32), sds((), i32))
        if self._chunk_fn is not None:
            # every prompt runs the one chunk program
            buckets = [self.prefill_chunk]
            warmed = [self._chunk_fn.warmup(
                params, pool_sds,
                tuple(sds(a.shape, a.dtype) for a in self._prefill_hist),
                self._chunk_where_shapes(),
                sds((1, self.prefill_chunk), i32),
                sds((), i32), *scalars)]
        else:
            buckets = sorted({prompt_bucket(t, self.max_context)
                              for t in prompt_lens})
            warmed = [self._admit_fn(tb).warmup(
                params, pool_sds, self.pager.prompt_pages_shapes(tb),
                sds((1, tb), i32), *scalars) for tb in buckets]
        compiled += sum(dt > 0 for dt in warmed)
        seconds += sum(warmed)
        if self.spec_k > 1:
            # the configured k is the one the live path runs; __init__
            # pinned it to the SPEC_KS grid so this warm covers it
            assert self.spec_k in SPEC_KS
            dt = self._spec_fn.warmup(
                params, pool_sds, sds((S, MP), i32), sds((S,), i32),
                sds((S,), jnp.bool_), sds((S,), i32),
                sds((S, self.spec_k - 1), i32))
            compiled += dt > 0
            seconds += dt
        if self.prefix_sharing:
            dt = self._cow_fn.warmup(pool_sds, sds((), i32),
                                     sds((), i32))
            compiled += dt > 0
            seconds += dt
            # a shared prefix can leave ANY suffix shorter than the
            # prompt, so warm the downward closure of the reachable
            # prompt buckets — admission order then never traces
            top = max(buckets) if buckets else 16
            sbuckets = sorted({prompt_bucket(t, self.max_context)
                               for t in range(1, top + 1)})
            for sb in sbuckets:
                dt = self._suffix_fn(sb).warmup(
                    params, pool_sds, sds((MP,), i32),
                    sds((1, sb), i32), sds((), i32), sds((), i32),
                    sds((), jnp.float32), sds((), jnp.float32),
                    sds((), i32))
                compiled += dt > 0
                seconds += dt
        return {"compiled": int(compiled), "seconds": seconds,
                "buckets": list(buckets), "spec_k": self.spec_k}
