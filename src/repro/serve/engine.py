"""Continuous-batching decode engine (single replica).

A fixed pool of ``max_batch`` slots shares one jitted batched decode_step
with a *per-slot position vector* — slots advance independently, so finished
sequences are replaced by queued requests immediately (continuous batching)
with no head-of-line blocking.  Prompts are teacher-forced through the decode
path token-by-token, which keeps a single compiled shape per engine — the
right trade for the CPU test harness.

The *bucketed prefill fast path* (``prefill``/``insert``) consumes a whole
prompt in one jitted call instead: prompts are right-padded to a power-of-two
length bucket (one compiled shape per bucket, block sizes from the autotune
registry via ``kernels/prefill``), the true last-token logits give the
first output token, and the resulting ``KVHandoff`` — request + first token +
batch-1 cache slice — can be ``insert()``-ed into a free slot of *any*
engine, including a different replica (prefill/decode disaggregation).

The engine reports throughput heartbeats which the fleet server's runtime
(fleet.py) consumes for cross-replica scope-length allotment.

With ``tracer`` set (an ``obs.Tracer``; the executor running the engine sets
it), each call is a span: ``engine.step`` with its phases ``.prep`` (slot
admission and the input arrays), ``.device`` (the decode program with its
greedy argmax, waited for), ``.fetch`` (the sampled tokens pulled to the
host: one int32 a slot, counted in ``bytes``) and ``.sample`` (the slot
bookkeeping), and counters ``active``, ``feeding`` (active slots that
consumed a prompt token and sampled nothing), ``sampled`` and
``max_batch``; ``engine.prefill`` with ``.device`` and ``.fetch``, and
``handoff_bytes`` (its ``KVHandoff``'s cache);
``engine.insert``.  Admission closes a request's ``request.queue`` span and
``insert`` its ``request.handoff``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.performance import PerfReport
from ..kernels.prefill.ops import length_bucket
from ..models.model import Model
from ..obs import NO_SPAN


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    submit_step: int = 0
    finish_step: int = 0


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0             # next cache index to write
    fed: int = 0             # prompt tokens already consumed


@dataclasses.dataclass
class KVHandoff:
    """A completed prefill: everything a decode replica needs to continue.

    ``caches`` is the batch-1 cache pytree covering positions [0, bucket);
    ``insert`` writes it into one slot lane of the target engine's full-size
    cache (positions beyond ``pos`` are never attended — decode masks
    ``arange(S) <= pos``).  ``first_token`` was sampled from the true
    last-prompt-position logits, so a handoff + decode reproduces the
    teacher-forced token sequence."""

    req: Request
    pos: int                 # cache positions filled (= len(prompt))
    first_token: int
    caches: object           # batch-1 cache pytree, seq dim = bucket
    source: str              # producing engine (provenance / debugging)
    bucket: int

    @property
    def nbytes(self) -> int:
        """Bytes of ``caches`` (the memory this handoff holds while it is
        kept for a re-insert)."""
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(self.caches))


def greedy_token(logits, vocab_size: int):
    """The first index of the largest logit over the true vocabulary (the
    padded tail excluded), as int32: ``logits`` ``(..., padded_vocab)``."""
    return jnp.argmax(logits[..., :vocab_size], axis=-1).astype(jnp.int32)


def decode_step(model: Model, params, caches, toks, pos):
    """The program ``DecodeEngine.step`` runs: ``model.decode_step`` and each
    slot's greedy token, so only ``(max_batch,)`` int32 leave the device.
    Returns ``(tokens, caches)``.  Jitted with ``model`` static and the cache
    donated (``static_argnums=0, donate_argnums=2``); ``model.decode_step``
    is looked up as it is traced."""
    logits, caches = model.decode_step(params, caches, toks, pos)
    return greedy_token(logits[:, 0], model.cfg.vocab_size), caches


# The engine's cache is donated, so an insert writes its lane in place
# instead of holding a second whole cache while it copies.
@functools.partial(jax.jit, donate_argnums=0)
def _insert_lane(caches, part, idx):
    """``caches`` with ``part`` (a batch-1 handoff cache) written into slot
    lane ``idx``: the batch axis is the first where the handoff slice is 1 and
    the engine cache is wider; the (shorter) bucket seq axis starts at 0.
    Garbage beyond the handoff's ``pos`` is never attended."""

    def put(full, p):
        starts = [0] * full.ndim
        for a in range(full.ndim):
            if p.shape[a] != full.shape[a] and p.shape[a] == 1:
                starts[a] = idx
                break
        return jax.lax.dynamic_update_slice(full, p.astype(full.dtype),
                                            tuple(starts))

    return jax.tree_util.tree_map(put, caches, part)


class DecodeEngine:
    def __init__(
        self, model: Model, params, max_batch: int = 4, max_seq: int = 128,
        eos_id: int | None = None, greedy: bool = True, seed: int = 0,
        name: str = "engine0",
    ):
        if model.cfg.input_mode == "embeds" and not model.cfg.is_enc_dec:
            raise ValueError("DecodeEngine drives token-input models")
        if model.cfg.is_enc_dec:
            raise ValueError("use the enc-dec serving path (examples) instead")
        self.model = model
        self.params = params
        self.name = name
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.greedy = greedy
        self.rng = np.random.default_rng(seed)
        self.slots = [_Slot() for _ in range(max_batch)]
        self.queue: list[Request] = []
        self.caches = model.init_cache(max_batch, max_seq)
        self._decode = jax.jit(decode_step, static_argnums=0, donate_argnums=2)
        self._prefills: dict[int, object] = {}   # bucket -> jitted prefill
        self.steps = 0
        self.tokens_out = 0
        self.prompt_fed = 0      # prompt tokens consumed (feed or prefill)
        self.handoffs_in = 0     # KVHandoffs inserted into this engine
        self.tracer = None       # obs.Tracer, set by the executor running it
        self._hb_steps = 0
        self._hb_tokens = 0
        self._hb_fed = 0

    # ----------------------------------------------------------------- admin
    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError("request exceeds engine max_seq")
        req.submit_step = self.steps
        self.queue.append(req)

    def _admit(self) -> None:
        tracer = self.tracer
        for slot in self.slots:
            if slot.req is None and self.queue:
                slot.req = self.queue.pop(0)
                slot.pos = 0
                slot.fed = 0
                if tracer is not None:
                    tracer.close("request.queue", slot.req.rid,
                                 worker=self.name)

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.req is not None)

    def cancel(self, rid: int) -> Request | None:
        """Withdraw an unfinished request (queued or mid-decode in a slot)
        and reset its decode state, so re-submitting it to another engine
        decodes it from scratch — the exactly-once guarantee when a request
        migrates off a killed engine mid-bundle.  Partial tokens this engine
        already produced are discarded (the request never *completed* here).
        Returns the request, or None if ``rid`` is unknown/already done."""
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                self.queue.pop(i)
                return r
        for slot in self.slots:
            r = slot.req
            if r is not None and r.rid == rid:
                slot.req = None
                slot.pos = 0
                slot.fed = 0
                r.out_tokens = []
                r.done = False
                r.finish_step = 0
                return r
        return None

    # --------------------------------------------------------------- prefill
    def prefill(self, req: Request) -> KVHandoff:
        """Consume the whole prompt in one bucketed jitted call.

        One compiled shape per power-of-two length bucket: the prompt is
        right-padded to the bucket and the true last-token logits are read at
        ``last_pos = L - 1`` (causality keeps valid positions exact under end
        padding).  Stateless w.r.t. the slot pool — the produced ``KVHandoff``
        is decoded wherever it gets ``insert``-ed."""
        L = len(req.prompt)
        if L == 0:
            raise ValueError("prefill needs a non-empty prompt")
        if L + req.max_new_tokens > self.max_seq:
            raise ValueError("request exceeds engine max_seq")
        bucket = length_bucket(L, self.max_seq)
        tracer = self.tracer
        span = NO_SPAN if tracer is None else tracer.span
        with span("engine.prefill", worker=self.name, rid=req.rid, length=L,
                  bucket=bucket) as pf:
            toks = np.zeros((1, bucket), np.int64)
            toks[0, :L] = req.prompt
            fn = self._prefills.get(bucket)
            if fn is None:
                model = self.model

                def run(params, toks, last_pos):
                    logits, caches = model.prefill(
                        params, {"tokens": toks}, last_pos=last_pos)
                    return greedy_token(logits[0, 0], model.cfg.vocab_size), caches

                fn = jax.jit(run)
                self._prefills[bucket] = fn
            with span("engine.prefill.device"):
                token, caches = fn(
                    self.params, jnp.asarray(toks, jnp.int32),
                    jnp.int32(L - 1)
                )
                token.block_until_ready()
            with span("engine.prefill.fetch"):
                first = (
                    int(token) if self.greedy
                    else int(self.rng.choice(self.model.cfg.vocab_size))
                )
            handoff = KVHandoff(req=req, pos=L, first_token=first,
                                caches=caches, source=self.name, bucket=bucket)
            if tracer is not None:
                pf.set(handoff_bytes=handoff.nbytes)
        self.prompt_fed += L
        self.tokens_out += 1
        return handoff

    def insert(self, handoff: KVHandoff) -> int:
        """Continue a prefilled request on this engine.  Returns the slot
        index, or -1 when the request finished *at* prefill (max_new_tokens
        == 1 or first token is EOS) and no slot is needed.

        Exactly-once contract: ``insert`` (re)sets ``out_tokens`` to the
        handoff's first token, so a decode cancelled mid-stream on a killed
        replica can re-insert the *same* handoff on the heir and decode a
        bitwise-identical continuation — the prefill is never recomputed and
        never double-counted."""
        tracer = self.tracer
        if tracer is None:
            return self._insert(handoff)
        tracer.close("request.handoff", handoff.req.rid, worker=self.name)
        with tracer.span("engine.insert", worker=self.name,
                         rid=handoff.req.rid, pos=handoff.pos,
                         bucket=handoff.bucket):
            return self._insert(handoff)

    def _insert(self, handoff: KVHandoff) -> int:
        r = handoff.req
        if len(r.prompt) + r.max_new_tokens > self.max_seq:
            raise ValueError("request exceeds engine max_seq")
        r.submit_step = self.steps
        r.out_tokens = [handoff.first_token]
        r.done = False
        self.handoffs_in += 1
        if r.max_new_tokens <= 1 or (
            self.eos_id is not None and handoff.first_token == self.eos_id
        ):
            r.done = True
            r.finish_step = self.steps
            return -1
        idx = next(
            (i for i, s in enumerate(self.slots) if s.req is None), None
        )
        if idx is None:
            raise RuntimeError(
                f"engine {self.name!r}: no free slot for handoff insert"
            )

        self.caches = _insert_lane(self.caches, handoff.caches,
                                   jnp.int32(idx))
        slot = self.slots[idx]
        slot.req = r
        slot.pos = handoff.pos
        slot.fed = len(r.prompt)
        return idx

    # ------------------------------------------------------------------ step
    def step(self) -> list[Request]:
        """Advance every active slot one token; returns finished requests.

        Idle slots re-write position 0 of their own cache lane with a pad
        token — harmless (the lane is reinitialized on admission by writing
        from pos 0 upward, and validity masks bound attention at pos)."""
        tracer = self.tracer
        span = NO_SPAN if tracer is None else tracer.span
        with span("engine.step", worker=self.name) as step:
            with span("engine.step.prep"):
                self._admit()
                active = self.active
                if active:
                    toks = np.zeros((self.max_batch, 1), np.int64)
                    pos = np.zeros((self.max_batch,), np.int64)
                    for i, slot in enumerate(self.slots):
                        r = slot.req
                        if r is None:
                            continue
                        pos[i] = slot.pos
                        if slot.fed < len(r.prompt):
                            toks[i, 0] = r.prompt[slot.fed]
                        else:
                            toks[i, 0] = r.out_tokens[-1]
            if active == 0:
                step.set(active=0, feeding=0, sampled=0,
                         max_batch=self.max_batch)
                return []
            with span("engine.step.device"):
                tokens, self.caches = self._decode(
                    self.model, self.params, self.caches,
                    jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
                )
                tokens.block_until_ready()
            self.steps += 1
            with span("engine.step.fetch") as fetch:
                tokens = np.asarray(tokens)
                fetch.set(bytes=tokens.nbytes)
            finished = []
            sampled = 0
            with span("engine.step.sample"):
                for i, slot in enumerate(self.slots):
                    r = slot.req
                    if r is None:
                        continue
                    slot.pos += 1
                    if slot.fed < len(r.prompt):
                        slot.fed += 1
                        self.prompt_fed += 1
                        if slot.fed < len(r.prompt):
                            continue  # still feeding prompt; no sample yet
                    nxt = (
                        int(tokens[i]) if self.greedy
                        else int(self.rng.choice(self.model.cfg.vocab_size))
                    )
                    r.out_tokens.append(nxt)
                    self.tokens_out += 1
                    sampled += 1
                    if (
                        len(r.out_tokens) >= r.max_new_tokens
                        or (self.eos_id is not None and nxt == self.eos_id)
                        or slot.pos >= self.max_seq
                    ):
                        r.done = True
                        r.finish_step = self.steps
                        finished.append(r)
                        slot.req = None
            step.set(active=active, feeding=active - sampled, sampled=sampled,
                     max_batch=self.max_batch)
        return finished

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        done: list[Request] = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self.active == 0 and not self.queue:
                break
        return done

    @property
    def throughput(self) -> float:
        return self.tokens_out / max(self.steps, 1)

    def heartbeat(self, now_s: float, seconds_per_step: float = 1.0) -> PerfReport | None:
        """Work/sec since the last heartbeat, as a PerfReport for the
        fleet server's tracker (the paper's background process).

        Work counts *prompt tokens consumed* as well as output tokens: a
        step spent teacher-forcing a prompt is real engine work, so a
        mid-prompt-feed window reports the engine's true speed instead of
        going silent (silence froze the tracker's perf estimate exactly when
        a new bundle landed — the early-estimate distortion).  Returns None
        when no engine steps ran since the last call."""
        steps = self.steps - self._hb_steps
        work = (self.tokens_out - self._hb_tokens) + (
            self.prompt_fed - self._hb_fed
        )
        if steps <= 0 or work <= 0:
            return None
        self._hb_steps, self._hb_tokens = self.steps, self.tokens_out
        self._hb_fed = self.prompt_fed
        return PerfReport(
            worker=self.name,
            work_done=float(work),
            elapsed_s=steps * seconds_per_step,
            time_s=now_s,
        )
