"""The generate engine: a decoder served step by step.

Beside :class:`~vilbert_multitask_tpu.engine.runtime.InferenceEngine`'s
contract (one forward, one answer) this engine's is *a step*: two program
families over one sequence state (``engine/seqstate.py``),

``prefill``  one chunk of one sequence's prompt, padded to a bucket of
             ``GenerateConfig.prefill_buckets`` tokens;
``decode``   one token of every running sequence, padded to a bucket of
             ``GenerateConfig.decode_buckets`` sequences;

both compiled, cached and warmed through the same machinery as the ViLBERT
programs: :class:`~vilbert_multitask_tpu.engine.runtime._AotProgram` over
``engine/aotcache.py`` under record keys of their own (``prefill/b2048/…``),
``vmt_engine_compiles_total`` counting real compiles, :meth:`warmup` running
every bucket before the first request.

The model is one of ``models/`` (:func:`model_module`: the module named by
the configuration's ``model_type``); everything here (buckets, donated
state, the AOT cache, run-ahead, ``collect``) is shared by all of them.

Nothing a step needs waits for the host. The state (what the model's
``state_layout`` says a slot holds, key/value pages, and each slot's last
token) is
donated to every call and taken back updated; the token a step generates
stays on the device as the next step's input. What the host wants of a step
(the token, its logit, the logits asked for: a few hundred bytes) is
fetched up to :data:`DECODE_RUN_AHEAD` steps late, after the later steps are
already queued on the device.

The scheduler (``serve/scheduler.py``) drives it: :meth:`admit`,
:meth:`prefill_next`, :meth:`decode`, :meth:`collect`. One thread calls
these; the engine itself starts none.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import threading
import time
from typing import Dict, List, Optional, Sequence as Seq

import jax
import jax.numpy as jnp
import numpy as np

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    GENERATE_TASK_ID,
    TASK_REGISTRY,
    FrameworkConfig,
    TaskSpec,
)
from vilbert_multitask_tpu.engine import aotcache
from vilbert_multitask_tpu.engine.runtime import _AotProgram, _COMPILES
from vilbert_multitask_tpu.engine.seqstate import Sequence, SequenceState
from vilbert_multitask_tpu.ops import paged_attention
from vilbert_multitask_tpu.resilience import ReplicaKilled

# Pages of its own sequence a step of the ``jax.numpy`` prefill attention
# reads at once (the kernel of ``ops/paged_attention.py`` reads one).
PREFILL_ATTENTION_PAGES = 2
# Decode steps dispatched before the oldest one's tokens are fetched: one
# keeps the device fed while the host fetches, two hides a slow fetch.
DECODE_RUN_AHEAD = 2

_PREFILL_TOKENS = obs.REGISTRY.counter(
    "vmt_prefill_tokens_total",
    "Prompt tokens dispatched in prefill chunks (padding not counted).")
_DECODE_TOKENS = obs.REGISTRY.counter(
    "vmt_decode_tokens_total",
    "Tokens dispatched in decode steps, one a running sequence a step.")
_PREFILL_FILL = obs.REGISTRY.histogram(
    "vmt_prefill_chunk_fill",
    "Prompt tokens of a prefill chunk as a share of its bucket.",
    labelnames=("bucket",),
    buckets=tuple(i / 16 for i in range(1, 17)))
_PREFILL_ATTENTION_CHUNKS = obs.REGISTRY.counter(
    "vmt_prefill_attention_chunks_total",
    "Prefill chunks dispatched, by what runs their attention over the "
    "sequence's pages: the Pallas kernel or the jax.numpy loop.",
    labelnames=("path",))
_PREFILL_ATTENTION_PAGES = obs.REGISTRY.counter(
    "vmt_prefill_attention_pages_total",
    "Page steps the prefill attention kernel was asked to walk: pages up "
    "to a query tile's last position, summed over a chunk's tiles and the "
    "paged layers.")
_POOL_FILL = obs.REGISTRY.histogram(
    "vmt_kv_pool_fill",
    "Key/value pages in use as a share of the pool, read at every decode "
    "step.",
    buckets=tuple(i / 16 for i in range(1, 17)))
_DECODE_FILL = obs.REGISTRY.histogram(
    "vmt_decode_batch_fill",
    "Running sequences of a decode step as a share of its bucket.",
    labelnames=("bucket",),
    buckets=tuple(i / 16 for i in range(1, 17)))


_MOE_LABELS = ("program",)
_MOE_CALLS = obs.REGISTRY.counter(
    "vmt_moe_calls_total",
    "Expert-layer calls dispatched: one a sparse layer a step.",
    labelnames=_MOE_LABELS)
_MOE_PAIRS = obs.REGISTRY.counter(
    "vmt_moe_pairs_total",
    "Token-expert pairs computed here (their expert's weights are held "
    "here).", labelnames=_MOE_LABELS)
_MOE_PAIRS_ROUTED = obs.REGISTRY.counter(
    "vmt_moe_pairs_routed_total",
    "Token-expert pairs routed, held here or not: experts a token times "
    "real tokens, a sparse layer.", labelnames=_MOE_LABELS)
_MOE_EXPERTS_TOUCHED = obs.REGISTRY.counter(
    "vmt_moe_experts_touched_total",
    "Held experts that got at least one pair, summed over expert-layer "
    "calls.", labelnames=_MOE_LABELS)
_MOE_LOAD = obs.REGISTRY.histogram(
    "vmt_moe_expert_load_max_over_mean",
    "An expert-layer call's fullest held expert over the mean of the held "
    "ones (1.0: even).", labelnames=_MOE_LABELS,
    buckets=(1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0, 32.0, 128.0))


_STEP_LABELS = ("program",)
_SELF_ROWS = obs.REGISTRY.counter(
    "vmt_self_decoder_rows_total",
    "Real rows a split model's self-decoder ran: prompt tokens and decoded "
    "tokens.", labelnames=_STEP_LABELS)
_CROSS_ROWS = obs.REGISTRY.counter(
    "vmt_cross_decoder_rows_total",
    "Real rows a split model's cross-decoder and head ran: one a finished "
    "prompt, and decoded tokens.", labelnames=_STEP_LABELS)
_SSM_TOKENS = obs.REGISTRY.counter(
    "vmt_ssm_scan_tokens_total",
    "Real tokens through a state-space layer's scan (prefill) or step "
    "(decode), summed over those layers.", labelnames=_STEP_LABELS)
_SHARED_KV_READS = obs.REGISTRY.counter(
    "vmt_shared_kv_page_reads_total",
    "Pages of the one pool that decode steps walked: pages in use times the "
    "layers that read them (the paged layer itself and every cross layer).")


def model_module(model_cfg):
    """The module of ``models/`` that serves this configuration: the one
    its ``model_type`` names (``param_shapes``, ``init_params``,
    ``state_layout``, ``prefill_chunk``, ``decode_step``, ``kernels_on``,
    ``PREFILL_GRANULE``). A module whose prefill stops half-way down the
    stack for every chunk but a prompt's last says ``PREFILL_SPLIT`` (its
    ``prefill_chunk`` then takes ``final``) and ``step_work(cfg)``, from
    which the ``vmt_self_decoder_*`` / ``vmt_ssm_*`` counters are
    reckoned."""
    return importlib.import_module(
        f"vilbert_multitask_tpu.models.{model_cfg.model_type}")


@dataclasses.dataclass
class GenerateRequest:
    """A validated ``generate`` job, as the engine wants it."""

    prompt: np.ndarray          # int32 [prompt_len]
    max_new_tokens: int
    logit_ids: np.ndarray       # int32 [max_logit_ids], zero-padded
    n_logit_ids: int
    spec: TaskSpec = TASK_REGISTRY[GENERATE_TASK_ID]
    # The engine fills these in as the sequence runs.
    seq: Optional[Sequence] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_logits: List[float] = dataclasses.field(default_factory=list)
    logits: List[List[float]] = dataclasses.field(default_factory=list)

    @property
    def complete(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens

    def result(self) -> "GenerateResult":
        return GenerateResult(self.tokens, self.token_logits, self.logits)


@dataclasses.dataclass
class GenerateResult:
    tokens: List[int]
    token_logits: List[float]
    logits: List[List[float]]
    kind: str = "generate"
    boxes = None

    def to_json(self) -> dict:
        return {"tokens": self.tokens, "token_logits": self.token_logits,
                "logits": self.logits}


class GenerateEngine:
    """One model, one sequence state, two program families."""

    generates = True
    mesh = None

    def __init__(self, cfg: FrameworkConfig, *, params=None, seed: int = 0,
                 replica_id: Optional[str] = None,
                 aot_cache: Optional[aotcache.AotCache] = None):
        gen = cfg.generate
        if gen.model is None:
            raise ValueError("GenerateEngine needs cfg.generate.model")
        self.cfg = cfg
        self.gen = gen
        self.model_cfg = gen.model
        self.model_lib = model_lib = model_module(gen.model)
        # None: every chunk runs the whole stack.
        self._split = (model_lib.step_work(gen.model)
                       if getattr(model_lib, "PREFILL_SPLIT", False) else None)
        self.replica_id = replica_id
        self.killed = False
        self.boot_times: Dict[str, float] = {}
        self._boot_lock = threading.Lock()
        page, granule = gen.page_size, model_lib.PREFILL_GRANULE
        for b in gen.prefill_buckets:
            if b % page or b % granule:
                raise ValueError(f"prefill bucket {b} is no multiple of the "
                                 f"page size {page} and the model's "
                                 f"{granule}")
        if params is None:
            with jax.transfer_guard("allow"):
                key = jax.random.PRNGKey(seed)
            params = model_lib.init_params(self.model_cfg, key,
                                           jnp.dtype(gen.param_dtype))
        t_up = time.perf_counter()
        self.params = jax.device_put(params)
        jax.block_until_ready(self.params)
        self.book_boot_time("upload_s", time.perf_counter() - t_up)
        self.seqstate = SequenceState(
            gen, model_lib.state_layout(self.model_cfg, gen.param_dtype))
        self.seqstate.allocate()
        # The AOT executable cache, on accelerators only: XLA:CPU cannot load
        # the prefill program back (its triangular solve is a LAPACK call
        # the serialized executable does not carry; the process dies).
        self._aot: Optional[aotcache.AotCache] = None
        if jax.default_backend() != "cpu":
            if aot_cache is not None:
                self._aot = aot_cache
            elif cfg.engine.aot_cache_dir:
                self._aot = aotcache.AotCache(cfg.engine.aot_cache_dir,
                                              generate_fingerprint(cfg))
        # keyed ('prefill'|'decode', bucket)
        self._programs: Dict[tuple, callable] = {}
        self._program_lock = threading.Lock()
        # Steps dispatched whose small outputs the host has not fetched.
        self._pending: collections.deque = collections.deque()

    # ------------------------------------------------------------ programs
    def _program(self, family: str, bucket: int):
        key = (family, bucket)
        with self._program_lock:
            if key in self._programs:
                return self._programs[key]
            cfg, gen, model_lib = self.model_cfg, self.gen, self.model_lib
            if family == "prefill":
                split = self._split is not None

                def run(params, state, x):
                    final = {"final": x["final"]} if split else {}
                    return model_lib.prefill_chunk(
                        cfg, params, state, x["tokens"], x["slot"],
                        x["start"], x["length"], x["page_row"],
                        x["logit_ids"],
                        attention_block=PREFILL_ATTENTION_PAGES, **final)
            else:
                block = min(gen.decode_attention_pages, gen.kv_pages)

                def run(params, state, x):
                    return model_lib.decode_step(
                        cfg, params, state, x["active"], x["positions"],
                        x["write_page"], x["page_slot"], x["page_pos"],
                        x["pool_blocks"], x["logit_ids"],
                        attention_block=block)
            # The executable's name in a profile: jit_prefill_step /
            # jit_decode_step.
            run.__name__ = f"{family}_step"
            step = jax.jit(run, donate_argnums=(1,))
            if self._aot is None:
                _COMPILES.inc(program=family)
                fn = step
            else:
                rec = aotcache.record_key(
                    family, bucket, gen.param_dtype, False,
                    aotcache.topology_id(self.cfg.mesh), False)
                fn = _AotProgram(self, family, bucket, False, step, rec)
            self._programs[key] = fn
            return fn

    def _host_inputs(self, family: str, bucket: int) -> dict:
        """The per-call inputs of one program, zeroed: the shapes and types
        a dispatch ships (``_abstract_forward_args``, the warm-up)."""
        st, n = self.seqstate, self.gen.max_logit_ids
        if family == "prefill":
            x = {"tokens": np.zeros((bucket,), np.int32),
                 "slot": np.int32(0), "start": np.int32(0),
                 "length": np.int32(0),
                 "page_row": np.full((st.max_pages_per_seq,), st.pages,
                                     np.int32),
                 "logit_ids": np.zeros((n,), np.int32)}
            if self._split is not None:
                x["final"] = np.bool_(False)
            return x
        return {"active": np.zeros((bucket,), np.bool_),
                "positions": np.zeros((bucket,), np.int32),
                "write_page": np.full((bucket,), st.pages, np.int32),
                "page_slot": np.full((st.pages,), -1, np.int32),
                "page_pos": np.zeros((st.pages,), np.int32),
                "pool_blocks": np.int32(0),
                "logit_ids": np.zeros((bucket, n), np.int32)}

    def _abstract_forward_args(self, family: str, bucket: int):
        def sds(x):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype)

        return (jax.tree_util.tree_map(sds, self.params),
                jax.tree_util.tree_map(sds, self.seqstate.arrays),
                jax.tree_util.tree_map(sds,
                                       self._host_inputs(family, bucket)))

    def book_boot_time(self, phase: str, seconds: float) -> None:
        with self._boot_lock:
            self.boot_times[phase] = (
                self.boot_times.get(phase, 0.0) + seconds)

    def _call(self, family: str, bucket: int, inputs: dict) -> dict:
        """Every device step funnels through here: the state goes in
        donated and comes back updated; the small outputs are returned
        still on the device."""
        if self.killed:
            raise ReplicaKilled(
                f"engine replica {self.replica_id or '?'} is dead")
        st = self.seqstate
        new_state, out = self._program(family, bucket)(
            self.params, st.arrays, jax.device_put(inputs))
        st.arrays = new_state
        return out

    def all_buckets(self) -> List[tuple]:
        return ([("prefill", b) for b in sorted(self.gen.prefill_buckets)]
                + [("decode", b) for b in sorted(self.gen.decode_buckets)])

    def warmup(self, buckets=None, parallel=None) -> None:
        """Compile (or load) and run every program once on a zeroed input:
        padding rows only, so the state is left as it was."""
        for family, bucket in self.all_buckets():
            out = self._call(family, bucket,
                             self._host_inputs(family, bucket))
            jax.block_until_ready(out)

    def boot_from_cache(self, buckets=None) -> bool:
        """Install every program from the AOT cache without compiling;
        True iff all of them were there (see ``InferenceEngine``'s)."""
        if self._aot is None:
            return False
        ok = True
        for family, bucket in self.all_buckets():
            fn = self._program(family, bucket)
            if isinstance(fn, _AotProgram):
                ok = (fn.ensure(load_only=True) is not None) and ok
        return ok

    @property
    def pallas_enabled(self) -> bool:
        return bool(self.model_lib.kernels_on(self.model_cfg))

    @property
    def input_cache_stats(self) -> Dict[str, int]:
        return {}

    def live_stats(self) -> Dict[str, float]:
        stats = {"engine_compiled": float(len(self._programs)),
                 "engine_killed": float(self.killed)}
        stats.update(self.seqstate.stats())
        for phase, seconds in dict(self.boot_times).items():
            stats[f"boot_{phase}"] = float(seconds)
        return stats

    @property
    def idle(self) -> bool:
        """No dispatched step is waiting for its tokens to be fetched."""
        return not self._pending

    def drop_pending(self) -> None:
        """Forget the steps not yet fetched (their sequences have failed)."""
        self._pending.clear()

    def close(self) -> None:
        """Drop the device state (the app has stopped)."""
        self.drop_pending()
        self.seqstate.drop_arrays()

    # ------------------------------------------------------------- requests
    def prepare_generate(self, body: dict) -> GenerateRequest:
        """Validate a job body (``serve/http_api.py`` checked the same at
        the door) and lay it out for the device."""
        prompt, new = body.get("prompt_ids"), body.get("max_new_tokens")
        ids = body.get("logit_ids") or []
        problem = self.gen.problem_with(prompt, new, ids)
        if problem:
            raise ValueError(problem)
        padded = np.zeros((self.gen.max_logit_ids,), np.int32)
        padded[:len(ids)] = ids
        return GenerateRequest(np.asarray(prompt, np.int32), new, padded,
                               len(ids))

    def admit(self, req: GenerateRequest) -> bool:
        """Reserve the request's slot and pages; False: ask again later."""
        seq = self.seqstate.admit(len(req.prompt), req.max_new_tokens)
        if seq is None:
            return False
        req.seq = seq
        return True

    def prefill_next(self, req: GenerateRequest) -> None:
        """Dispatch the next chunk of the request's prompt. The chunk that
        ends the prompt yields the first generated token."""
        seq, gen = req.seq, self.gen
        left = seq.prompt_len - seq.prefilled
        bucket = gen.prefill_bucket_for(left)
        n = min(left, bucket)
        with obs.span("engine.prefill", tokens=n, bucket=bucket):
            tokens = np.zeros((bucket,), np.int32)
            tokens[:n] = req.prompt[seq.prefilled:seq.prefilled + n]
            x = {"tokens": tokens, "slot": np.int32(seq.slot),
                 "start": np.int32(seq.prefilled), "length": np.int32(n),
                 "page_row": self.seqstate.page_row(seq),
                 "logit_ids": req.logit_ids}
            if self._split is not None:
                x["final"] = np.bool_(n == left)
            out = self._call("prefill", bucket, x)
        _PREFILL_TOKENS.inc(n)
        self._count_split("prefill", n, int(n == left))
        _PREFILL_FILL.observe(n / bucket, bucket=str(bucket))
        self._count_prefill_attention(seq.prefilled, bucket)
        seq.prefilled += n
        self.seqstate.note_written(seq, seq.prefilled)
        if not seq.prefilling:
            seq.generated = 1
            self._pending.append((out, [req], None, "prefill", n))
        elif "moe" in out:
            # A chunk that ends no prompt yields no token; its expert
            # counts are still fetched, with the later steps'.
            self._pending.append(({"moe": out["moe"]}, [], None, "prefill",
                                  n))

    def decode(self, reqs: Seq[GenerateRequest]) -> None:
        """Dispatch one token of each of ``reqs`` (all prefilled, none
        done). The step covers the slots up to the highest of theirs, in
        the smallest bucket that holds them; row b is slot b."""
        st, gen = self.seqstate, self.gen
        bucket = gen.decode_bucket_for(max(r.seq.slot for r in reqs) + 1)
        with obs.span("engine.decode_step", batch=len(reqs), bucket=bucket):
            x = self._host_inputs("decode", bucket)
            for req in reqs:
                seq = req.seq
                # The token fed is the last one generated: it sits at
                # position prompt + generated - 1.
                position = seq.prompt_len + seq.generated - 1
                x["active"][seq.slot] = True
                x["positions"][seq.slot] = position
                x["write_page"][seq.slot] = st.page_of(seq, position)
                x["logit_ids"][seq.slot] = req.logit_ids
                st.note_written(seq, position + 1)
            # Copies: the manager changes its tables in place at the next
            # admit or release, and the transfer of this call's inputs may
            # still be reading them then.
            x["page_slot"] = st.page_slot.copy()
            x["page_pos"] = st.page_pos.copy()
            x["pool_blocks"] = np.int32(st.pool_blocks(
                min(gen.decode_attention_pages, gen.kv_pages)))
            out = self._call("decode", bucket, x)
        _DECODE_TOKENS.inc(len(reqs))
        _DECODE_FILL.observe(len(reqs) / bucket, bucket=str(bucket))
        in_use = st.stats()["kv_pages_in_use"]
        _POOL_FILL.observe(in_use / st.pages)
        self._count_split("decode", len(reqs), len(reqs), pages=in_use)
        for req in reqs:
            req.seq.generated += 1
        self._pending.append((out, list(reqs), [r.seq.slot for r in reqs],
                              "decode", len(reqs)))

    def collect(self, drain: bool = False) -> List[GenerateRequest]:
        """Fetch the outputs of steps dispatched ``DECODE_RUN_AHEAD`` steps
        ago or earlier (all of them with ``drain``) and hand their tokens
        to their requests. Returns the requests this completed."""
        finished = []
        keep = 0 if drain else DECODE_RUN_AHEAD
        while len(self._pending) > keep:
            out, reqs, rows, program, tokens = self._pending.popleft()
            with obs.span("engine.result_wait", steps_behind=keep):
                host = jax.device_get(out)
            if "moe" in host:
                self._count_experts(program, tokens, host["moe"])
            for k, req in enumerate(reqs):
                pick = ((lambda a: a) if rows is None
                        else (lambda a, b=rows[k]: a[b]))
                req.tokens.append(int(pick(host["token"])))
                req.token_logits.append(float(pick(host["token_logit"])))
                req.logits.append(
                    [float(v) for v in
                     pick(host["logits"])[:req.n_logit_ids]])
                if req.complete:
                    finished.append(req)
        return finished

    def _count_prefill_attention(self, start: int, bucket: int) -> None:
        """One chunk's attention over its sequence's pages into the
        ``vmt_prefill_attention_*`` counters, reckoned here from what the
        call was given: the kernel's tile follows from the bucket and the
        layout, the pages a tile walks from where the chunk starts."""
        if not self.pallas_enabled:
            _PREFILL_ATTENTION_CHUNKS.inc(path="xla")
            return
        st = self.seqstate
        _PREFILL_ATTENTION_CHUNKS.inc(path="kernel")
        _PREFILL_ATTENTION_PAGES.inc(
            st.layout.paged_layers * paged_attention.prefill_pages_walked(
                start, bucket, st.page_size, st.layout.query_group,
                st.max_pages_per_seq))

    def _count_split(self, program: str, rows: int, cross_rows: int,
                     pages: float = 0) -> None:
        """One step of a split model into its counters: ``rows`` real rows
        ran the self-decoder (and every state-space layer), ``cross_rows``
        of them the cross-decoder and the head: what the call was told
        (``final``), not what the design says; a decode step walked the
        ``pages`` in use once a layer that reads the one pool."""
        if self._split is None:
            return
        _SELF_ROWS.inc(rows, program=program)
        _CROSS_ROWS.inc(cross_rows, program=program)
        _SSM_TOKENS.inc(rows * self._split["ssm_layers"], program=program)
        _SHARED_KV_READS.inc(pages * self._split["pool_readers"])

    def _count_experts(self, program: str, tokens: int, moe) -> None:
        """One step's expert-layer integers ``moe`` [sparse layers, 3]
        (pairs computed here, experts touched, the fullest expert's pairs)
        into the ``vmt_moe_*`` instruments; ``tokens`` real tokens went
        through every sparse layer."""
        cfg = self.model_cfg
        held = cfg.held[1]
        _MOE_CALLS.inc(len(moe), program=program)
        _MOE_PAIRS.inc(int(moe[:, 0].sum()), program=program)
        _MOE_PAIRS_ROUTED.inc(
            len(moe) * tokens * cfg.num_experts_per_tok, program=program)
        _MOE_EXPERTS_TOUCHED.inc(int(moe[:, 1].sum()), program=program)
        for pairs, _, fullest in moe:
            if pairs:
                _MOE_LOAD.observe(float(fullest) * held / float(pairs),
                                  program=program)

    def release(self, req: GenerateRequest) -> None:
        """Free the request's slot and pages. Safe as soon as its last
        step is dispatched: the device runs the steps in order, and every
        later step takes the state this one returned."""
        if req.seq is not None:
            self.seqstate.release(req.seq)


def generate_fingerprint(cfg: FrameworkConfig) -> dict:
    """The AOT cache's compatibility fingerprint for the generate
    programs: the shared one plus everything of ``GenerateConfig``, the
    model's ``model_type`` among it: one model's executables are never read
    for another's."""
    fp = aotcache.compile_fingerprint(cfg, mesh=None, heads=False)
    fp["generate"] = dataclasses.asdict(cfg.generate)
    return fp
