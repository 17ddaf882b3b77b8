"""Batched generation engine: prefill + greedy/temperature decode.

Two batching disciplines share one model and one decode computation:

* :meth:`Engine.generate` — the **static batch**: requests are padded into
  one lockstep batch; ragged prompts are right-padded and each row's first
  token is sampled from its own last real prompt token (see
  ``transformer.prefill``'s ``last_positions``); rows that emit ``eos_id``
  keep decoding into a waste slot and their waste tokens are masked out.
* :meth:`Engine.slots` — **continuous batching**: a fixed pool of slots over
  one persistent batched KV cache with *per-slot positions*.
  :meth:`SlotPool.admit` prefills one prompt into a free slot (batch-1
  prefill, cache rows written in place), :meth:`SlotPool.step` decodes every
  slot at its own depth in one lockstep step, and :meth:`SlotPool.evict`
  frees a slot the moment its row finishes — a late request takes over the
  freed row mid-stream while the other slots keep decoding. This is the
  substrate ``repro.traffic``'s scheduler drives (docs/traffic.md).

This is also the program the serving-path characterization prices:
``ServingCostProbe`` lowers :meth:`Engine.lower_prefill` /
:meth:`Engine.lower_decode` HLO and pairs the estimator's prediction with
the measured wall clock (docs/serving.md).

Known approximation (static batch only): after prefill, decode steps use one
shared position counter for the whole batch, so a short row's later tokens
sit at the padded batch's positions, and its KV slots between
``len(prompt)`` and the batch's ``max_len`` hold pad-token entries. The slot
pool does not share this: every slot carries its own position.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from repro import tracing
from repro.models import transformer
from repro.models.config import ModelConfig, Runtime


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray          # [B, max_new]; waste slots masked to eos_id
    prompt_lens: np.ndarray
    steps: int                  # decode steps actually run (early-exit aware)
    finished_steps: np.ndarray | None = None  # per-row eos step, -1 = never


class Engine:
    def __init__(self, params: Any, cfg: ModelConfig, rt: Runtime,
                 *, max_len: int = 512):
        self.params = params
        self.cfg = cfg
        self.rt = rt
        self.max_len = max_len

        # named functions, so that the profiler's modules read jit_prefill
        # and jit_decode_step
        def prefill(p, t, last):
            return transformer.prefill(p, cfg, rt, tokens=t,
                                       last_positions=last)

        def decode_step(p, c, t, pos):
            return transformer.decode_step(p, c, t, pos, cfg, rt)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step, donate_argnums=(1,))

    def generate(self, prompts: list[list[int]], *, max_new: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: int | None = None) -> GenerateResult:
        b = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        plen = int(lens.max())
        toks = np.zeros((b, plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p    # right-padded; per-row gather below
        logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                      jnp.asarray(lens - 1))
        cache = transformer.pad_cache(cache, self.cfg, plen + max_new)
        key = jax.random.PRNGKey(seed)
        out = np.zeros((b, max_new), np.int32)
        finished = np.full((b,), -1, np.int32)
        tok = _sample(logits, temperature, key)
        steps = 0
        for step in range(max_new):
            t = np.asarray(tok)[:, 0]
            out[:, step] = t
            steps = step + 1
            if eos_id is not None:
                finished = np.where((t == eos_id) & (finished < 0),
                                    step, finished)
            if step == max_new - 1:
                break
            if eos_id is not None and (finished >= 0).all():
                break               # every row done: stop burning waste slots
            logits, cache = self._decode(self.params, cache, jnp.asarray(tok),
                                         plen + step)
            key = jax.random.fold_in(key, step)
            tok = _sample(logits, temperature, key)
        if eos_id is not None:
            # waste-slot masking: a finished row keeps decoding in the static
            # batch; everything after its eos is noise, not output
            col = np.arange(max_new)[None, :]
            done = finished[:, None]
            out = np.where((done >= 0) & (col > done), eos_id, out)
        return GenerateResult(tokens=out, prompt_lens=lens, steps=steps,
                              finished_steps=finished if eos_id is not None
                              else None)

    # ---------------------------------------------------- characterization
    def lower_prefill(self, batch: int, prompt_len: int):
        """Lower the prefill computation at one ``(batch, prompt_len)`` cell.

        Returns ``(lowered, args)``: the jit-lowered prefill (``.compile()``
        yields the executable and its optimized HLO text) plus the concrete
        arrays to run it with — what ``ServingCostProbe`` prices and times.
        """
        toks = jnp.reshape(
            jnp.arange(batch * prompt_len, dtype=jnp.int32)
            % max(self.cfg.vocab_size, 1), (batch, prompt_len))
        last = jnp.full((batch,), prompt_len - 1, jnp.int32)
        args = (self.params, toks, last)
        return self._prefill.lower(*args), args

    def lower_decode(self, batch: int, prompt_len: int,
                     max_len: int | None = None):
        """Lower one decode step at a cell (cache sized ``max_len``, position
        ``prompt_len`` — the first generated token's step).

        ``max_len`` defaults to the engine's configured capacity
        (``Engine.max_len``) — the cache the serving loop actually decodes
        against — not a prompt-derived size: a cell priced at
        ``prompt_len + 32`` would measure a different (smaller) KV scan than
        the one production steps pay for. Callers needing the old footprint
        pass it explicitly; the priced cache size is recorded in the cell's
        notes either way.

        Uses a *non-donating* jit so the probe can execute the compiled step
        repeatedly against the same cache buffer while timing.
        """
        max_len = max_len if max_len is not None else self.max_len
        cache = transformer.init_cache(self.cfg, batch, max_len,
                                       self.cfg.cdtype)
        toks = jnp.zeros((batch, 1), jnp.int32)
        cfg, rt = self.cfg, self.rt

        def decode_step(p, c, t):
            return transformer.decode_step(p, c, t, prompt_len, cfg, rt)

        fn = jax.jit(decode_step)
        args = (self.params, cache, toks)
        return fn.lower(*args), args

    # ------------------------------------------------------- slot-level API
    def slots(self, n_slots: int, *, max_len: int | None = None) -> "SlotPool":
        """A continuous-batching slot pool over this engine's model."""
        return SlotPool(self, n_slots,
                        max_len=max_len if max_len is not None else self.max_len)


def _sample(logits: jax.Array, temperature: float, key) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature)[:, None].astype(jnp.int32)


def write_slot(cache, pc, slot):
    """``pc``, a batch-1 prefill cache, written into row ``slot`` of the
    pool's ``cache``."""
    return jax.tree_util.tree_map(
        lambda big, small: lax.dynamic_update_slice(
            big, small.astype(big.dtype), (0, slot) + (0,) * (big.ndim - 2)),
        cache, pc)


@dataclasses.dataclass
class _Slot:
    """Book-keeping for one row of the pool's persistent batch."""

    uid: int = -1                 # caller-supplied request id, -1 = free
    pos: int = 0                  # next KV write index == current kv_len
    n_generated: int = 0
    active: bool = False


class SlotPool:
    """Continuous batching over one persistent batched KV cache.

    The pool owns a ``[periods, n_slots, max_len, ...]`` cache and a per-slot
    position vector. :meth:`admit` runs a batch-1 prefill for one prompt and
    writes its cache rows into the slot in place (``dynamic_update_slice`` on
    the batch axis — the other slots' rows are untouched, so in-flight
    requests never notice an admission); :meth:`step` runs **one** lockstep
    decode step for the whole pool with per-slot positions (the
    ``attn_decode`` per-row scatter path); :meth:`evict` frees the slot
    immediately — its stale KV rows are invisible to attention (masked by the
    per-slot ``kv_len``) and are overwritten by the next admission.

    Free slots still occupy their row of the static batch (the decode step's
    shape never changes — that is what makes it one compiled executable);
    their garbage tokens are simply never surfaced. Greedy decoding is
    deterministic per slot regardless of what the other slots hold;
    ``temperature > 0`` sampling derives each slot's PRNG stream from
    ``(seed, uid, n_generated)`` so a request's sample path is independent of
    which slot it landed in and what was co-batched with it.
    """

    def __init__(self, engine: Engine, n_slots: int, *, max_len: int,
                 temperature: float = 0.0, seed: int = 0):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.engine = engine
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.cache = transformer.init_cache(engine.cfg, self.n_slots,
                                            self.max_len, engine.cfg.cdtype)
        self._slots = [_Slot() for _ in range(self.n_slots)]
        self._tok = np.zeros((self.n_slots, 1), np.int32)  # last sampled token
        # admit writes the batch-1 prefill cache into one slot's rows; the
        # pool cache is donated (replaced wholesale every admit/step)
        self._write = jax.jit(write_slot, donate_argnums=(0,))

    # ------------------------------------------------------------- queries
    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if not s.active]

    def active_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s.active]

    def position(self, slot: int) -> int:
        return self._slots[slot].pos

    # ------------------------------------------------------------ lifecycle
    def admit(self, slot: int, prompt: list[int], *, uid: int = 0,
              max_new: int = 1) -> int:
        """Prefill ``prompt`` into a free ``slot``; returns the first token.

        The first generated token is sampled from the prefill logits — by the
        time admit returns, the request's TTFT is complete. ``max_new`` is
        only validated here (the scheduler enforces the budget); the prompt
        plus budget must fit the pool's ``max_len``.
        """
        st = self._slots[slot]
        if st.active:
            raise ValueError(f"slot {slot} is occupied (uid={st.uid})")
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds the "
                f"pool's max_len ({self.max_len})")
        eng = self.engine
        with tracing.span("repro.pool.admit", uid=uid, prompt_len=len(prompt)):
            with tracing.span("repro.pool.prefill"):
                toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
                last = jnp.asarray([len(prompt) - 1], jnp.int32)
                logits, pc = eng._prefill(eng.params, toks, last)
            with tracing.span("repro.pool.write"):
                self.cache = self._write(self.cache, pc, slot)
            st.uid, st.pos, st.n_generated, st.active = uid, len(prompt), 0, True
            with tracing.span("repro.pool.first_token"):
                tok = int(np.asarray(self._sample_slot(logits, st))[0, 0])
        self._tok[slot, 0] = tok
        # pos stays at len(prompt): the first generated token's KV is written
        # by the *next* decode step, at exactly that position
        st.n_generated = 1
        return tok

    def evict(self, slot: int) -> None:
        """Free ``slot`` immediately; its KV rows stay as invisible garbage
        (masked by per-slot kv_len) until the next admission overwrites them."""
        self._slots[slot] = _Slot()

    def step(self) -> np.ndarray:
        """One lockstep decode step for the whole pool; returns ``[n_slots]``
        tokens. Only the active slots' tokens are meaningful — free slots keep
        decoding garbage into their own (unread) rows, exactly the static
        batch's waste-slot behavior, because the compiled step's shape is
        fixed at ``n_slots``."""
        active = sum(s.active for s in self._slots)
        if not active:
            raise ValueError("step() with no active slot")
        eng = self.engine
        # the slot bookkeeping after the read-back is the step's self time
        with tracing.span("repro.pool.step", active=active):
            with tracing.span("repro.pool.step_inputs"):
                positions = [s.pos for s in self._slots]
                # each row's kv_len (pos + 1): the positions decode attention
                # reads; it fetches whole blocks, so this undercounts its
                # reads by less than a block a row
                tracing.count("pool.kv_rows_live",
                              sum(positions) + self.n_slots)
                pos = jnp.asarray(positions, jnp.int32)
                tok = jnp.asarray(self._tok)
            with tracing.span("repro.pool.decode"):
                logits, self.cache = eng._decode(eng.params, self.cache, tok,
                                                 pos)
            with tracing.span("repro.pool.tokens"):  # waits for the device
                out = np.asarray(
                    jnp.argmax(logits, axis=-1).astype(jnp.int32)).copy()
            if self.temperature > 0.0:
                # sample only the occupied rows: free slots keep their greedy
                # garbage (never surfaced), and their sentinel uid must not
                # consume — or crash — a PRNG stream
                with tracing.span("repro.pool.sample"):
                    for i, st in enumerate(self._slots):
                        if st.active:
                            row = _sample(logits[i:i + 1], self.temperature,
                                          self._slot_key(st))
                            out[i] = int(np.asarray(row)[0, 0])
            for i, st in enumerate(self._slots):
                self._tok[i, 0] = out[i]
                if st.active:
                    st.pos += 1
                    st.n_generated += 1
        return out

    # ------------------------------------------------------------- sampling
    def _slot_key(self, st: _Slot):
        # uid folded mod 2^32: callers may use negative sentinel uids
        # (EngineExecutor.warm admits uid=-1) and fold_in takes uint32 data
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 st.uid % (1 << 32))
        return jax.random.fold_in(key, st.n_generated)

    def _sample_slot(self, logits: jax.Array, st: _Slot) -> jax.Array:
        return _sample(logits, self.temperature,
                       self._slot_key(st) if self.temperature > 0 else None)
