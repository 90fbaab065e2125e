"""Batched serving engines: LM (prefill + decode) and plan-driven CNN.

The LM engine compiles two functions per (batch, prompt_len) signature:

  * ``prefill``  -- processes the whole prompt batch, filling the cache;
  * ``decode``   -- one token for every sequence in the batch against the
    cache, cache donated (in-place on device).

Two decode modes:

  * uniform (``generate``): one scalar cursor for the whole batch -- every
    row was prefilled together and advances in lockstep;
  * per-row (``decode_rows`` + ``new_batch_cache``): the cache cursor is a
    (B,) vector, rows sit at ragged positions, and retired rows are masked
    (their cursor frozen, their sample discarded).  This is the substrate
    of the continuous-batching scheduler
    (``repro.serve.scheduler.ContinuousBatchingScheduler``), which admits,
    retires and re-admits requests into slots mid-stream; DESIGN.md SS7
    has the invariants.

Sampling: greedy or temperature, always over the *real* vocab columns
(padded logits sliced off).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.api import ModelApi, cache_scatter_row, vector_pos_cache


class CacheOverflowError(ValueError):
    """A prompt + generation budget that cannot fit the KV cache.

    Raised (instead of silently corrupting the cache tail) by
    ``ServeEngine.generate`` and by scheduler admission; carries the
    offending lengths.
    """

    def __init__(self, *, prompt_len: int, max_new_tokens: int, max_len: int):
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.max_len = max_len
        super().__init__(
            f"prompt_len={prompt_len} + max_new_tokens={max_new_tokens} = "
            f"{prompt_len + max_new_tokens} exceeds cache max_len={max_len}")


class ServeEngine:
    def __init__(self, api: ModelApi, params: Any, *, max_len: int):
        self.api = api
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(
            lambda p, batch, cache: api.prefill(p, batch, cache))
        # chunked prefill runs the same prefill trace per chunk but donates
        # the row cache (each chunk rewrites it in place; the caller always
        # replaces its reference with the returned cache)
        self._prefill_chunk = jax.jit(
            lambda p, batch, cache: api.prefill(p, batch, cache),
            donate_argnums=(2,))
        self._decode = jax.jit(
            lambda p, tok, cache: api.decode_step(p, tok, cache),
            donate_argnums=(2,))
        self._decode_masked = jax.jit(self._decode_rows_impl,
                                      donate_argnums=(2,))
        self._decode_sampled = jax.jit(self._decode_rows_sampled_impl,
                                       donate_argnums=(2,),
                                       static_argnames=("greedy",))

    def _decode_rows_impl(self, p, tok, cache, active):
        logits, new_cache = self.api.decode_step(p, tok, cache)
        # retired rows: freeze the cursor.  Their (dummy) token was still
        # written at the frozen position -- harmless, because admission
        # replaces the ENTIRE row (cache_scatter_row) before reuse -- and a
        # frozen cursor keeps long-idle slots from walking off the cache.
        new_cache = dict(new_cache)
        new_cache["pos"] = jnp.where(active, new_cache["pos"], cache["pos"])
        return logits, new_cache

    def _decode_rows_sampled_impl(self, p, tok, cache, active, keys, temps,
                                  greedy=False):
        """Fused steady-state step: masked decode + per-row RNG-chain split
        + per-row sample + masked token update, one dispatch (the scheduler
        hot loop -- eager per-step glue would cost several host round
        trips per generated token).  ``greedy`` (static) elides the
        categorical draw when every live row samples at temperature 0; the
        key chains still advance so a later non-greedy step stays on the
        solo sequence."""
        logits, new_cache = self._decode_rows_impl(p, tok, cache, active)
        nxt = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        new_keys, subs = nxt[:, 0], nxt[:, 1]
        lg = logits[..., : self.api.cfg.vocab]
        if greedy:
            toks = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            def one(l, key, t):
                safe = jnp.where(t > 0, t, 1.0)
                draw = jax.random.categorical(key, l / safe, axis=-1)
                return jnp.where(t > 0, draw, jnp.argmax(l, axis=-1))

            toks = jax.vmap(one)(lg, subs, temps).astype(jnp.int32)
        new_tok = jnp.where(active[:, None], toks[:, None], tok)
        return toks, new_tok, new_keys, new_cache

    def _sample(self, logits: jax.Array, key, temperature: float) -> jax.Array:
        logits = logits[..., : self.api.cfg.vocab]
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / temperature, axis=-1)

    def generate(
        self,
        prompts: jax.Array,                # (B, S_prompt) int32
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        extras: dict | None = None,        # modality extras for prefill
    ) -> jax.Array:
        """Returns (B, max_new_tokens) generated ids."""
        B, S = prompts.shape
        if S + max_new_tokens > self.max_len:
            raise CacheOverflowError(prompt_len=S,
                                     max_new_tokens=max_new_tokens,
                                     max_len=self.max_len)
        cache = self.api.init_cache(B, self.max_len)
        batch = {"tokens": prompts, **(extras or {})}
        logits, cache = self._prefill(self.params, batch, cache)
        # split BEFORE the first sample: the root key must never be both
        # consumed by a sample and split for the chain (key reuse would
        # correlate the first token with the second draw)
        key, sub = jax.random.split(jax.random.PRNGKey(seed))
        out = []
        tok = self._sample(logits, sub, temperature)
        for i in range(max_new_tokens):
            out.append(tok)
            if i == max_new_tokens - 1:
                break
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, tok[:, None], cache)
            tok = self._sample(logits, sub, temperature)
        return jnp.stack(out, axis=1)

    # --------------- per-row-cursor surface (continuous batching) ---------------

    def new_batch_cache(self, slots: int):
        """Fresh cache with a (slots,) per-row cursor vector (all zero)."""
        return vector_pos_cache(self.api.init_cache(slots, self.max_len),
                                slots)

    def new_row_cache(self):
        """Fresh single-row cache (the chunked-prefill substrate)."""
        return self.api.init_cache(1, self.max_len)

    def prefill_row_chunk(self, tokens: jax.Array, row_cache,
                          extras: dict | None = None):
        """Advance ONE prompt chunk against a single-row cache.

        tokens: (1, c) int32 -- the next ``c`` prompt tokens.  The cache
        cursor supplies the chunk's base position (RoPE angles, cache
        writes and causal masks all key off ``cache["pos"]``), so feeding
        a prompt chunk-by-chunk through this call is the SAME computation
        a one-shot prefill performs, just sliced along the query axis.
        Returns (last logits (1, V), cache); the cache argument is
        donated.  Intermediate chunks' logits are cheap -- the model
        prefills unembed only the final position -- and are discarded by
        callers until the final chunk.
        """
        batch = {"tokens": tokens, **(extras or {})}
        return self._prefill_chunk(self.params, batch, row_cache)

    def prefill_row(self, prompt: jax.Array, extras: dict | None = None,
                    *, chunk: int | None = None):
        """Prefill ONE request into a fresh single-row cache.

        prompt: (S,) or (1, S) int32.  Returns (last logits (1, V), row
        cache) -- exactly the state a solo ``generate`` of this prompt
        would hold before its first sample, which is what makes scheduler
        streams bitwise-identical to solo runs.

        ``chunk`` processes the prompt ``chunk`` tokens at a time through
        the same per-chunk trace the scheduler's interleaved prefill uses
        (modality extras force the one-shot path: they describe the whole
        prompt and cannot be sliced along the token axis).
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        cache = self.new_row_cache()
        S = prompt.shape[1]
        if chunk is None or extras or S <= chunk:
            batch = {"tokens": prompt, **(extras or {})}
            return self._prefill(self.params, batch, cache)
        logits = None
        for s0 in range(0, S, chunk):
            logits, cache = self.prefill_row_chunk(
                prompt[:, s0:s0 + chunk], cache)
        return logits, cache

    def adopt_row(self, batch_cache, row_cache, slot):
        """Scatter a prefilled single-row cache into slot ``slot``."""
        return cache_scatter_row(batch_cache, row_cache, slot)

    def decode_rows(self, tok: jax.Array, cache, active: jax.Array):
        """One decode step with per-row cursors and a (B,) active mask.

        Inactive (retired / never-admitted) rows run dead compute but
        their cursors do not advance; callers discard their logits.
        Returns (logits (B, V_eff), cache).  The cache argument is donated.
        """
        return self._decode_masked(self.params, tok, cache, active)

    def decode_rows_sampled(self, tok, cache, active, keys, temps,
                            greedy=False):
        """Fused decode + per-row sample (the scheduler's steady-state
        call): returns (sampled (B,), next tok (B,1), next keys, cache).
        Per-row sampling follows the solo ``generate`` chain exactly:
        ``key, sub = split(key)``, greedy rows argmax, others categorical
        with their own sub-key.  The cache argument is donated.
        """
        return self._decode_sampled(self.params, tok, cache, active,
                                    keys, temps, greedy=greedy)

    def decode_throughput_probe(self, batch: int, steps: int = 8) -> float:
        """tokens/sec for pure decode at the engine's max_len (benchmark)."""
        import time

        cache = self.api.init_cache(batch, self.max_len)
        tok = jnp.zeros((batch, 1), jnp.int32)
        logits, cache = self._decode(self.params, tok, cache)  # compile
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(steps):
            logits, cache = self._decode(self.params, tok, cache)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        return batch * steps / dt


class ConvServeEngine:
    """Batched CNN inference engine built on the ConvPlan layer.

    The production argument for a single planning layer (DESIGN.md SS5):
    a serving engine sees the same layer shapes millions of times, so
    algorithm/F(m,r)/blocking/mode selection must be *resolved once and
    cached*, not re-derived per request.  Here every stride-1 3x3 conv in
    ``forward`` routes through ``conv2d(algorithm="auto")``, whose
    decisions come from the lru-cached ``repro.core.plan.plan``; this
    engine adds the per-input-signature jit cache on top, so steady-state
    requests pay zero selection or tracing cost.

    ``forward(params, images, *, algorithm=...)`` is any of the
    ``models.cnn`` forwards (or a compatible callable).

    ``mesh`` scales the engine out: the image batch is sharded over the
    mesh's "data" axis -- a ragged batch is zero-padded up to the axis
    multiple and the logits cropped, the same edge treatment as the
    executor's ragged T/C/K extents (zero images cost dead flops, never
    replicated compute) -- and, via ``repro.parallel.executor.use_mesh``
    at trace time, every Winograd-eligible conv inside ``forward``
    executes its Winograd-domain GEMM under shard_map with the plan's
    per-layer parallel mode.  The jit cache entry keeps its sharded form
    (keyed on the PADDED shape, so ragged batches share the aligned
    entry), and steady-state requests pay neither selection nor
    re-partitioning cost.

    ``parallel_mode`` forces one executor mode on every in-scope conv
    (``None`` leaves the per-layer choice to ``ConvPlan.parallel_mode``,
    the production setting; the mode-sweep tests and benchmarks force it).
    """

    def __init__(self, forward, params: Any, *, algorithm: str = "auto",
                 mesh=None, parallel_mode: str | None = None):
        self.forward = forward
        self.params = params
        self.algorithm = algorithm
        self.mesh = mesh
        self.parallel_mode = parallel_mode
        self._compiled: dict = {}

    def _shard_batch(self, images: jax.Array) -> jax.Array:
        """Zero-pad the batch to the "data"-axis multiple and lay it out."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        dp = self.mesh.shape.get("data", 1)
        pad = -images.shape[0] % dp
        if pad:
            images = jnp.pad(images, ((0, pad),) + ((0, 0),) * (images.ndim - 1))
        return jax.device_put(images, NamedSharding(self.mesh, P("data")))

    def _jitted(self, images: jax.Array):
        """The jitted forward for this input signature (one per signature)."""
        key = (tuple(images.shape), str(images.dtype))
        fn = self._compiled.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(self.forward,
                                           algorithm=self.algorithm))
            self._compiled[key] = fn
        return fn

    def _mesh_scope(self):
        from repro.parallel.executor import use_mesh

        return (use_mesh(self.mesh, self.parallel_mode)
                if self.mesh is not None else contextlib.nullcontext())

    def infer(self, images: jax.Array) -> jax.Array:
        """(B, H, W, C) -> logits; compiles once per input signature."""
        B = images.shape[0]
        if self.mesh is not None:
            images = self._shard_batch(images)
        with self._mesh_scope():
            out = self._jitted(images)(self.params, images)
        return out[:B] if out.shape[0] != B else out

    def lower(self, images: jax.Array):
        """The forward that ``infer`` runs for this batch, lowered the same
        way (sharded and under the mesh scope) -- for inspecting what it
        compiles to, e.g. ``engine.lower(x).compile().as_text()``."""
        if self.mesh is not None:
            images = self._shard_batch(images)
        with self._mesh_scope():
            return self._jitted(images).lower(self.params, images)

    @property
    def compiled_signatures(self) -> int:
        return len(self._compiled)


class CoalescingConvServeEngine:
    """Request-coalescing front on ``ConvServeEngine``.

    Concurrent CNN requests (single images or small ragged batches) are
    merged into ONE padded, mesh-sharded batch and the per-request results
    scattered back.  The coalescing key is (per-image shape, dtype,
    algorithm): requests sharing it also share every layer's cached
    ConvPlan and -- after the merged batch is zero-padded to the mesh's
    "data"-axis multiple -- the engine's padded-shape jit entry, so N
    requests pay one selection-free, pre-partitioned dispatch (DESIGN.md
    SS7).  Requests with different keys cannot share a trace and flush as
    separate batches.

    Usage: ``submit(images) -> ticket`` queues a request;
    ``flush() -> {ticket: logits}`` runs every queued group coalesced.

    ``max_coalesce`` caps how many rows MERGING may accumulate per
    dispatch (a cache-pressure bound); a group larger than the cap
    flushes as several merged batches.  Requests are never split, so a
    single request larger than the cap still dispatches whole.
    """

    def __init__(self, forward, params: Any, *, algorithm: str = "auto",
                 mesh=None, parallel_mode: str | None = None,
                 max_coalesce: int | None = None):
        self.engine = ConvServeEngine(forward, params, algorithm=algorithm,
                                      mesh=mesh, parallel_mode=parallel_mode)
        self.max_coalesce = max_coalesce
        self._pending: dict[tuple, list[tuple[int, jax.Array]]] = {}
        self._next_ticket = 0
        self.coalesced_dispatches = 0
        self.coalesced_requests = 0

    def coalesce_key(self, images: jax.Array) -> tuple:
        return (tuple(images.shape[1:]), str(images.dtype),
                self.engine.algorithm)

    @property
    def pending_requests(self) -> int:
        return sum(len(g) for g in self._pending.values())

    def submit(self, images: jax.Array) -> int:
        """Queue one request ((H,W,C) image or (n,H,W,C) batch) -> ticket."""
        images = jnp.asarray(images)
        if images.ndim == 3:
            images = images[None]
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.setdefault(self.coalesce_key(images), []).append(
            (ticket, images))
        return ticket

    def _dispatch(self, group: list[tuple[int, jax.Array]]) -> dict:
        merged = (group[0][1] if len(group) == 1
                  else jnp.concatenate([im for _, im in group], axis=0))
        logits = self.engine.infer(merged)
        out, ofs = {}, 0
        for ticket, im in group:
            out[ticket] = logits[ofs:ofs + im.shape[0]]
            ofs += im.shape[0]
        self.coalesced_dispatches += 1
        self.coalesced_requests += len(group)
        return out

    def flush(self) -> dict[int, jax.Array]:
        """Run every queued request, coalesced per key -> {ticket: logits}."""
        results: dict[int, jax.Array] = {}
        for _, group in sorted(self._pending.items(), key=lambda kv: str(kv[0])):
            chunk: list[tuple[int, jax.Array]] = []
            rows = 0
            for item in group:
                if (self.max_coalesce and chunk
                        and rows + item[1].shape[0] > self.max_coalesce):
                    results.update(self._dispatch(chunk))
                    chunk, rows = [], 0
                chunk.append(item)
                rows += item[1].shape[0]
            if chunk:
                results.update(self._dispatch(chunk))
        self._pending.clear()
        return results

    def infer(self, images: jax.Array) -> jax.Array:
        """Uncoalesced passthrough (the per-request baseline)."""
        return self.engine.infer(images)
