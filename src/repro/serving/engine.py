"""Serving engine: batched generation with bucketed prefill compilation,
a shared engine pool, and the cascade-server facade.

``GenerationEngine`` replaces the old per-``(seq_len, max_len)`` jit
cache — which recompiled on every new shape the tier-by-tier compaction
produced — with *bucketed* compilation: batch, prompt length and cache
length are rounded up to power-of-two buckets, so the number of compiled
prefill variants is logarithmic in the shape range instead of linear in
the number of distinct request shapes.

Exactness of the bucketing (all verified by tests/test_serving.py):
  * batch padding    — extra rows are computed and sliced off; always exact.
  * cache (max_len)  — decode masks slots beyond the fill level (full
    attention) or by ring-slot position (sliding), so a larger cache is
    always exact.
  * prompt padding   — right-pad tokens, read prefill logits at the true
    last position, start decode at the true length so pad slots are
    overwritten before the mask admits them. Exact for attention-only
    stacks whose ring cache never truncates the padded prompt; engines
    fall back to exact prompt shapes for SSM/hybrid stacks or when the
    sliding window is smaller than the padded prompt.
With ``temperature > 0`` every generated token — including the
post-prefill one, sampled from the prefill logits — goes through the
keyed categorical path and is seed-reproducible per bucket shape (the
noise tensor follows the padded shape); greedy decoding is bit-exact
regardless of bucketing.

Generation is split into two entry points so the scheduler can overlap
tiers (speculative cascade execution, ``repro.serving.sched``):
``prefill_async`` dispatches the prefill and returns a cancellable
``PrefillFuture`` — the sampled post-prefill token plus the KV-cache
handle, still potentially in flight thanks to jax async dispatch —
and ``decode_from`` consumes the future (KV handoff) and runs the
decode loop. ``generate`` is exactly their composition, so the split
is bit-identical by construction. ``PrefillFuture.cancel`` retires a
speculation: the cache/token references are dropped so the device
buffers free, and the pool (``EnginePool.speculate``) untracks it.

Telemetry (``repro.core.telemetry``): ``prefill_async`` runs under the
``engine.prefill`` span and ``decode_from`` under ``engine.decode``, each
step split into ``engine.decode.fetch`` (the host blocked on the token)
and ``engine.decode.dispatch`` (the host enqueueing the next step). Both
add their host seconds and counts to the chunk record open in the
caller's context, and nothing when none is; a prefill whose attention
runs in the flash kernel (``transformer.prefill_flash``, decided per
bucket) also counts as a ``prefill_flash_calls``.

``CascadeServer`` is the serving facade over the repo's single cascade
executor (``repro.core.cascade.execute_cascade``); the full three-strategy
pipeline (cache + prompt adaptation + cascade) lives in
``repro.serving.pipeline``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core import telemetry
from repro.core.cascade import CascadeTier, execute_cascade
from repro.models import attention
from repro.models import transformer as T


def bucket_size(x: int, floor: int) -> int:
    """Next power of two >= x, floored at ``floor`` — keeps the number of
    compiled shape variants O(log range) instead of O(distinct shapes)."""
    b = max(1, floor)
    while b < x:
        b *= 2
    return b


@dataclasses.dataclass
class PrefillFuture:
    """Cancellable handle to one dispatched prefill.

    Holds the post-prefill sampled token and the KV-cache handle (both
    jax arrays, possibly still computing — dispatch is async), plus the
    shape/seed bookkeeping ``decode_from`` needs to continue exactly
    where ``generate`` would. Exactly one of three things happens to a
    future: it is *committed* (``engine.decode_from`` — KV handoff into
    the decode loop), *cancelled* (``cancel`` — the device references
    are dropped so XLA can free the cache buffers; a cancelled
    speculation is never charged because its consumer never ran), or
    leaked with the engine (GC retires it). Commit and cancel both fire
    the one-shot ``_retire_cb`` so an owning ``EnginePool`` can untrack
    the in-flight speculation.
    """

    engine: "GenerationEngine"
    n_new: int
    b: int                      # true batch rows (callers see [:b])
    b_b: int                    # padded batch bucket
    s: int                      # true prompt length
    max_len: int                # KV-cache bucket length
    seed: int = 0
    cancelled: bool = False
    consumed: bool = False
    _tok: object = None         # (b_b, 1) int32 post-prefill token
    _cache: object = None       # KV-cache pytree (the handoff handle)
    _rkey: object = None        # PRNG state after the post-prefill sample
    _retire_cb: object = None   # pool untrack hook, fired exactly once

    @property
    def live(self) -> bool:
        """Still holding device state: neither committed nor cancelled."""
        return not (self.cancelled or self.consumed)

    def cancel(self):
        """Retire the speculation: drop the KV cache and token references
        (jax frees the device buffers once nothing holds them) and
        untrack from the owning pool. Idempotent; a no-op on a future
        already consumed by ``decode_from``."""
        if not self.live:
            return
        self.cancelled = True
        self._tok = self._cache = self._rkey = None
        self._retire()

    def _retire(self):
        cb, self._retire_cb = self._retire_cb, None
        if cb is not None:
            cb(self)


@dataclasses.dataclass
class GenerationEngine:
    """Batched prefill+decode generation for one model, bucket-compiled."""

    cfg: ModelConfig
    params: dict
    max_new_tokens: int = 16
    temperature: float = 0.0
    batch_floor: int = 8        # batch sizes bucketed to pow2 >= this
    seq_floor: int = 16         # prompt/cache lengths bucketed likewise
    pad_token: int = 0
    # pin this engine to one jax.Device (sharding.placement): params are
    # committed there, so prefill/decode — and the KV cache between
    # decode steps — run and stay on that device. None = default device.
    device: object | None = None
    # ... or shard it over a mesh slice (sharding.tier_mesh): params are
    # sharded per sharding.rules (FSDP over "data", tensor axes over
    # "model"), activations over batch, KV caches over heads, and every
    # prefill/decode runs as a pjit-sharded computation on the slice.
    # The layer stack is folded (models.transformer.fold_stack) so the
    # whole depth scans as one stacked leaf — compile count stays O(1)
    # in depth. Mutually exclusive with ``device``.
    mesh: object | None = None

    def __post_init__(self):
        if self.mesh is not None and self.device is not None:
            raise ValueError("pass device= or mesh=, not both")
        if self.mesh is not None:
            from repro.sharding import tier_mesh
            self.cfg, self.params = T.fold_stack(self.cfg, self.params)
            self._param_shardings = tier_mesh.tier_param_shardings(
                self.params, self.mesh)
            self.params = jax.device_put(self.params, self._param_shardings)
        cfg = self.cfg
        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)
        self._prefill_fns: dict[tuple[int, int, int], Callable] = {}
        # per bucket: whether its prefill's attention runs in the flash
        # kernel, by the test the model applies while tracing it
        self._prefill_flash: dict[tuple[int, int, int], bool] = {}
        self.compile_stats = {"prefill_compiles": 0, "prefill_calls": 0}

        def _decode_body(params, cache, tok, pos, key):
            logits, cache = T.decode_step(params, cache, tok, pos, cfg)
            logits = logits[:, -1]
            if self.temperature > 0:
                nxt = jax.random.categorical(key, logits / self.temperature)
            else:
                nxt = jnp.argmax(logits, -1)
            return nxt[:, None].astype(jnp.int32), cache

        self._decode_body = _decode_body
        self._decode = jax.jit(_decode_body)
        # mesh-sharded decode variants, keyed by (batch, cache) bucket:
        # unlike the single-device jit above (shardings propagate from
        # committed inputs), the pjit path pins in/out shardings so the
        # KV-cache layout is *stable* across the prefill -> decode
        # handoff — a PrefillFuture's cache re-enters decode with
        # exactly the layout prefill committed, never a GSPMD re-guess
        self._decode_fns: dict[tuple[int, int], Callable] = {}
        self.decode_shardings: dict[tuple[int, int], tuple] = {}

    def _seq_paddable(self, seq_bucket: int) -> bool:
        """Right-padding the prompt is exact iff every mixer is attention
        and no sliding-window ring buffer would evict padded-prompt slots
        before decode overwrites them (i.e. padded prompt fits the window).
        """
        specs = self.cfg.layers
        if any(not s.mixer.startswith("attn") for s in specs):
            return False
        if self.cfg.window and any(s.mixer == "attn_sliding" for s in specs):
            return seq_bucket < self.cfg.window
        return True

    def bucket_key(self, b: int, s: int, n_new: int) -> tuple[int, int, int]:
        """(batch, prompt, cache) buckets a (b, s) prompt generating
        ``n_new`` tokens is compiled and run at."""
        s_b = bucket_size(s, self.seq_floor)
        if not self._seq_paddable(s_b):
            s_b = s
        return (bucket_size(b, self.batch_floor), s_b,
                bucket_size(s_b + n_new, self.seq_floor))

    def _prefill_fn(self, key: tuple[int, int, int]) -> Callable:
        b_b, s_b, max_len = key
        if key not in self._prefill_fns:
            self.compile_stats["prefill_compiles"] += 1
            # GSPMD partitions a mesh-sharded prefill, and cannot
            # partition a Mosaic kernel: it keeps the jnp attention
            sharded = self.mesh is not None

            def fn(p, toks, last):
                with attention.partitioned(sharded):
                    return T.prefill(p, {"tokens": toks}, self.cfg,
                                     max_len=max_len, last_index=last)

            with attention.partitioned(sharded):
                self._prefill_flash[key] = T.prefill_flash(self.cfg, s_b)

            if self.mesh is None:
                self._prefill_fns[key] = jax.jit(fn)
            else:
                # pjit over the tier's slice: NamedSharding in/out
                # shardings per bucket key (batch over "data", KV cache
                # per sharding.rules — heads over "model" when they
                # divide it), so GSPMD never has to guess a layout.
                from repro.sharding import rules, tier_mesh
                tok_sh = tier_mesh.batch_sharding(self.mesh, b_b)
                rep = jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec())
                logits_s, cache_s = jax.eval_shape(
                    fn, self.params,
                    jax.ShapeDtypeStruct((b_b, s_b), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))
                out_sh = (rules.logits_sharding(self.mesh, self.cfg, b_b),
                          rules.cache_shardings(cache_s, self.mesh,
                                                self.cfg))
                self._prefill_fns[key] = jax.jit(
                    fn,
                    in_shardings=(self._param_shardings, tok_sh, rep),
                    out_shardings=out_sh)
        return self._prefill_fns[key]

    def _decode_fn(self, b_b: int, max_len: int, cache) -> Callable:
        """The decode step for one (batch, cache) bucket: the shared jit
        on a single device; on a mesh, a pjit variant with in/out
        shardings pinned to the prefill's committed layout (tokens over
        "data", KV cache per ``sharding.rules``) so the cache layout
        cannot drift across decode steps or the prefill->decode
        handoff."""
        if self.mesh is None:
            return self._decode
        key = (b_b, max_len)
        if key not in self._decode_fns:
            from repro.sharding import rules, tier_mesh
            tok_sh = tier_mesh.batch_sharding(self.mesh, b_b)
            rep = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            cache_sh = rules.cache_shardings(cache, self.mesh, self.cfg)
            self.decode_shardings[key] = (tok_sh, cache_sh)
            self._decode_fns[key] = jax.jit(
                self._decode_body,
                in_shardings=(self._param_shardings, cache_sh, tok_sh,
                              rep, rep),
                out_shardings=(tok_sh, cache_sh))
        return self._decode_fns[key]

    def prefill_async(self, tokens: np.ndarray, n_new: int | None = None,
                      seed: int = 0) -> PrefillFuture:
        """Dispatch the prefill for ``tokens`` (B, S) and return a
        cancellable ``PrefillFuture``. jax dispatch is asynchronous, so
        this returns as soon as the prefill (and the post-prefill token
        sample, which follows the exact keyed path ``generate`` uses) is
        enqueued on the engine's device/mesh — the caller overlaps it
        with other work and later either commits (``decode_from``) or
        cancels (``PrefillFuture.cancel``)."""
        if n_new is None:                  # NOT `or`: an explicit 0 is 0
            n_new = self.max_new_tokens
        b, s = tokens.shape
        if n_new <= 0:
            return PrefillFuture(self, n_new=0, b=b, b_b=b, s=s,
                                 max_len=0, seed=seed)
        key = self.bucket_key(b, s, n_new)
        b_b, s_b, max_len = key

        t0 = time.perf_counter()
        with telemetry.span(telemetry.PREFILL):
            toks = np.full((b_b, s_b), self.pad_token, tokens.dtype)
            toks[:b, :s] = tokens
            toks[b:, :s] = tokens[-1]      # batch filler: replicate a row

            self.compile_stats["prefill_calls"] += 1
            fn = self._prefill_fn(key)
            if self.mesh is not None:
                # the across-slice-boundary hop: host-compacted batches
                # are device_put onto the tier's slice, batch split
                # over "data"
                from repro.sharding import tier_mesh
                toks_dev = jax.device_put(
                    toks, tier_mesh.batch_sharding(self.mesh, b_b))
            else:
                toks_dev = jnp.asarray(toks)
            logits, cache = fn(self.params, toks_dev, jnp.int32(s - 1))
            rkey = jax.random.PRNGKey(seed)
            last_logits = logits[:, -1]
            if self.temperature > 0:
                # the post-prefill token goes through the same keyed
                # categorical path as every later token — not argmax
                rkey, sub = jax.random.split(rkey)
                nxt = jax.random.categorical(sub,
                                             last_logits / self.temperature)
            else:
                nxt = jnp.argmax(last_logits, -1)
            nxt = nxt[:, None].astype(jnp.int32)
        rec = telemetry.current()
        if rec is not None:
            rec.prefill_calls += 1
            rec.prefill_flash_calls += int(self._prefill_flash[key])
            rec.prefill_dispatch_s += time.perf_counter() - t0
        return PrefillFuture(self, n_new=n_new, b=b, b_b=b_b, s=s,
                             max_len=max_len, seed=seed, _tok=nxt,
                             _cache=cache, _rkey=rkey)

    def decode_from(self, fut: PrefillFuture) -> np.ndarray:
        """Commit a ``PrefillFuture``: take the KV-cache handoff and run
        the decode loop to ``(B, n_new)`` generated tokens — bit-identical
        to the ``generate`` call the future's ``prefill_async`` started,
        because it *is* the second half of that call."""
        if fut.engine is not self:
            raise ValueError("PrefillFuture belongs to a different engine")
        if fut.cancelled:
            raise RuntimeError("cannot decode a cancelled PrefillFuture "
                               "(its KV cache was retired)")
        if fut.consumed:
            raise RuntimeError("PrefillFuture already consumed")
        fut.consumed = True
        if fut.n_new <= 0:
            fut._retire()
            return np.zeros((fut.b, 0), np.int32)
        nxt, cache, rkey = fut._tok, fut._cache, fut._rkey
        fut._tok = fut._cache = fut._rkey = None
        fut._retire()
        decode = self._decode_fn(fut.b_b, fut.max_len, cache)
        # per step: host blocked on the token (fetch), then host work up
        # to the next step enqueued (dispatch); two clock reads a step
        fetch_s = dispatch_s = 0.0
        with telemetry.span(telemetry.DECODE):
            t = time.perf_counter()
            with TraceAnnotation(telemetry.DECODE_FETCH):
                out = [np.asarray(nxt)]
            t_host = time.perf_counter()
            fetch_s += t_host - t
            for i in range(fut.n_new - 1):
                with TraceAnnotation(telemetry.DECODE_DISPATCH):
                    rkey, sub = jax.random.split(rkey)
                    nxt, cache = decode(self.params, cache, nxt,
                                        jnp.int32(fut.s + i), sub)
                t = time.perf_counter()
                dispatch_s += t - t_host
                with TraceAnnotation(telemetry.DECODE_FETCH):
                    out.append(np.asarray(nxt))
                t_host = time.perf_counter()
                fetch_s += t_host - t
        rec = telemetry.current()
        if rec is not None:
            rec.decode_steps += fut.n_new - 1
            rec.decode_dispatch_s += dispatch_s
            rec.decode_fetch_s += fetch_s
        return np.concatenate(out, axis=1)[:fut.b]

    def generate(self, tokens: np.ndarray, n_new: int | None = None,
                 seed: int = 0) -> np.ndarray:
        """tokens (B, S) -> generated (B, n_new). Exactly
        ``decode_from(prefill_async(...))`` — the split entry points the
        speculative scheduler drives are the same code path."""
        return self.decode_from(self.prefill_async(tokens, n_new, seed))


@dataclasses.dataclass
class EnginePool:
    """Shared ``GenerationEngine`` pool: one engine (and so one bucketed
    jit cache) per model config, reused by every tier/pipeline that serves
    that model."""

    max_new_tokens: int = 16
    temperature: float = 0.0

    def __post_init__(self):
        self._engines: dict[tuple, GenerationEngine] = {}
        self._params_refs: dict[tuple, dict] = {}
        # in-flight speculative PrefillFutures, tracked per engine key
        # (i.e. per tier×placement) so an idle device's speculations can
        # be cancelled wholesale when the real accept mask lands.
        self._speculative: dict[tuple, list] = {}
        self.spec_stats = {"issued": 0, "committed": 0, "cancelled": 0}

    @staticmethod
    def _key(cfg: ModelConfig, params: dict, device=None, mesh=None) -> tuple:
        # key on weight identity too: two tiers can share an architecture
        # (same cfg.name) with different trained params, and must not
        # silently serve each other's model. The pool itself pins the
        # caller's pytree (_params_refs) so id(params) cannot be
        # recycled for the key's lifetime — a device-pinned engine
        # rebinds its params to the device copy and must not be the one
        # carrying that guarantee. Device — or mesh-slice device set +
        # shape — is part of the key: the same weights pinned to two
        # devices or sharded over two slices (sharding.placement /
        # sharding.tier_mesh) are distinct engines with independent
        # NamedSharding-keyed jit caches and KV-cache residency.
        if mesh is not None:
            where = ("mesh", mesh.devices.shape,
                     tuple(int(d.id) for d in mesh.devices.flat))
        elif device is not None:
            where = (device.platform, device.id)
        else:
            where = None
        return (cfg.name, id(params), where)

    def get(self, cfg: ModelConfig, params: dict,
            device=None, mesh=None) -> GenerationEngine:
        key = self._key(cfg, params, device, mesh)
        eng = self._engines.get(key)
        if eng is None:
            eng = GenerationEngine(cfg, params,
                                   max_new_tokens=self.max_new_tokens,
                                   temperature=self.temperature,
                                   device=device, mesh=mesh)
            self._engines[key] = eng
            self._params_refs[key] = params
        return eng

    def speculate(self, cfg: ModelConfig, params: dict,
                  tokens: np.ndarray, n_new: int | None = None,
                  seed: int = 0, device=None, mesh=None) -> "PrefillFuture":
        """Dispatch a *speculative* prefill on the (tier, placement)
        engine and track the future. The caller later resolves it with
        ``commit`` (runs the decode — now charged work) or ``cancel``
        (retires the KV cache — only wall-clock was burnt). Both paths
        untrack the future via its retire hook."""
        key = self._key(cfg, params, device, mesh)
        eng = self.get(cfg, params, device=device, mesh=mesh)
        fut = eng.prefill_async(tokens, n_new, seed)
        fut._retire_cb = lambda f, key=key: self._untrack(key, f)
        self._speculative.setdefault(key, []).append(fut)
        self.spec_stats["issued"] += 1
        return fut

    def commit(self, fut: "PrefillFuture") -> np.ndarray:
        """Commit a tracked speculation: KV handoff into the decode loop,
        returning the generated tokens ``generate`` would have."""
        if not fut.live:
            raise RuntimeError("cannot commit a retired PrefillFuture")
        self.spec_stats["committed"] += 1
        return fut.engine.decode_from(fut)

    def cancel(self, fut: "PrefillFuture") -> None:
        """Cancel a tracked speculation, retiring its KV cache."""
        if not fut.live:
            return
        self.spec_stats["cancelled"] += 1
        fut.cancel()

    def cancel_all(self, cfg: ModelConfig = None, params: dict = None,
                   device=None, mesh=None) -> int:
        """Cancel every live speculative future — for one engine key when
        ``cfg``/``params`` are given, across the whole pool otherwise
        (shutdown). Returns how many were cancelled."""
        if cfg is not None:
            keys = [self._key(cfg, params, device, mesh)]
        else:
            keys = list(self._speculative)
        n = 0
        for key in keys:
            for fut in list(self._speculative.get(key, ())):
                if fut.live:
                    self.cancel(fut)
                    n += 1
        return n

    def inflight(self) -> int:
        """Live (neither committed nor cancelled) speculative futures."""
        return sum(len(v) for v in self._speculative.values())

    def _untrack(self, key: tuple, fut: "PrefillFuture") -> None:
        lst = self._speculative.get(key)
        if lst is not None:
            try:
                lst.remove(fut)
            except ValueError:
                pass
            if not lst:
                self._speculative.pop(key, None)

    def __len__(self) -> int:
        return len(self._engines)

    @property
    def compile_stats(self) -> dict:
        """Aggregate prefill compile/call counts across the pool."""
        out = {"prefill_compiles": 0, "prefill_calls": 0}
        for eng in self._engines.values():
            for k in out:
                out[k] += eng.compile_stats[k]
        return out


@dataclasses.dataclass
class Tier:
    name: str
    answer: Callable            # tokens (n, L) -> answers (n,)
    cost: Callable              # tokens (n, L) -> per-query cost (n,)


def generation_tier(name: str, engine: GenerationEngine, price,
                    decode_answer: Callable, n_new: int = 1,
                    pad_token: int = 0) -> Tier:
    """A cascade tier backed by a pooled ``GenerationEngine``.

    decode_answer(generated (b, n_new)) -> answer ids (b,);
    price: ``ApiCost`` used for exact token-count accounting.
    """

    def answer(tokens: np.ndarray) -> np.ndarray:
        return np.asarray(decode_answer(engine.generate(tokens, n_new)))

    def cost(tokens: np.ndarray) -> np.ndarray:
        n_in = (tokens != pad_token).sum(-1)
        return np.asarray(price.query_cost(n_in, np.full_like(n_in, n_new)))

    return Tier(name, answer, cost)


@dataclasses.dataclass
class CascadeServer:
    """FrugalGPT cascade as a serving policy (tier-by-tier compaction).

    Thin facade over the repo's single cascade executor; use
    ``repro.serving.pipeline.ServingPipeline`` for the full
    cache + prompt-adaptation + cascade request path.
    """

    tiers: Sequence[Tier]
    thresholds: Sequence[float]         # len = len(tiers) - 1
    scorer: Callable                    # (tokens, answers) -> scores (n,)
    batch_size: int = 256

    def serve(self, tokens: np.ndarray) -> dict:
        ct = [CascadeTier(t.name, lambda q, t=t: (t.answer(q), t.cost(q)))
              for t in self.tiers]
        res = execute_cascade(ct, self.thresholds,
                              lambda q, a, _j: self.scorer(q, a),
                              tokens, batch_size=self.batch_size)
        return {
            "answers": np.asarray(res["answers"]).astype(np.int32),
            "cost": res["cost"],
            "stopped_at": res["stopped_at"],
            "tier_counts": [c for c in res["tier_counts"]],
        }
