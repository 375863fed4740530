"""Data pipeline — the paper's §4 'data handling module' adapted.

The paper dedicates a hardware thread so pre-processing never starves the
compute library.  Here a background thread fills a bounded queue with
host-side numpy batches (double buffering), and batches are placed onto the
mesh with the batch-dim sharding before the step consumes them.

Its spans (``repro.telemetry``, the process recorder unless one is given):
``data.wait`` is the whole hand-off of a batch (``Prefetcher.__next__``),
``data.place`` its child, the placer call, and ``data.h2d`` runs from the
start of placement until the host-to-device copy of every array of the
batch has landed.  On a mesh of more than one device the copy lands on the
default device and is then spread over the mesh: ``data.reshard`` runs from
that spread's dispatch until the placed batch is ready, so it also waits
for the steps queued on the device ahead of it.  Placement only queues the
copies (the runtime lays the batch out for the device on its own threads),
so each of the two spans is timed by a long-lived observer thread of its
own that blocks on readiness; the training thread never waits for them.

Streams are synthetic but deterministic (seeded): LM token streams with a
Zipf-ish unigram plus a learnable bigram structure (so losses actually fall),
image/label streams for the CNNs, frame/senone streams for CD-DNN, and the
VLM/audio composites (including MusicGen's codebook delay pattern).
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterator, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.telemetry.events import process_recorder


# ---------------------------------------------------------------------------
# synthetic sources (deterministic)
# ---------------------------------------------------------------------------
def lm_token_stream(vocab: int, batch: int, seq: int,
                    seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Markov-ish token stream: learnable bigram structure."""
    rng = np.random.default_rng(seed)
    V = int(vocab)
    shift = rng.integers(1, V, size=()).item()
    while True:
        first = rng.integers(0, V, size=(batch, 1))
        noise = rng.random((batch, seq - 1)) < 0.15
        toks = [first]
        for t in range(1, seq):
            nxt = (toks[-1] * 31 + shift) % V
            rand = rng.integers(0, V, size=(batch, 1))
            toks.append(np.where(noise[:, t - 1: t], rand, nxt))
        yield {"tokens": np.concatenate(toks, axis=1).astype(np.int32)}


def image_stream(image_size: int, num_classes: int, batch: int,
                 seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Images whose class determines a planted frequency pattern."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:image_size, 0:image_size].astype(np.float32)
    while True:
        labels = rng.integers(0, num_classes, size=(batch,))
        freq = (labels[:, None, None] + 1).astype(np.float32)
        base = np.sin(freq * xx[None] / image_size * 6.28) \
            + np.cos(freq * yy[None] / image_size * 6.28)
        img = base[..., None] + 0.3 * rng.standard_normal(
            (batch, image_size, image_size, 3)).astype(np.float32)
        yield {"images": img.astype(np.float32),
               "labels": labels.astype(np.int32)}


def asr_frame_stream(input_dim: int, num_senones: int, batch: int,
                     seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    proto = rng.standard_normal((num_senones, input_dim)).astype(np.float32)
    while True:
        sen = rng.integers(0, num_senones, size=(batch,))
        frames = proto[sen] + 0.5 * rng.standard_normal(
            (batch, input_dim)).astype(np.float32)
        yield {"frames": frames.astype(np.float32),
               "senones": sen.astype(np.int32)}


def vlm_stream(cfg: ModelConfig, batch: int, seq_txt: int,
               seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    from repro.models.frontends import mrope_positions
    rng = np.random.default_rng(seed)
    lm = lm_token_stream(cfg.vocab_size, batch, seq_txt, seed)
    s_img = cfg.vision_tokens
    grid_w = max(1, int(np.sqrt(s_img)))
    pos = np.asarray(mrope_positions(batch, s_img, seq_txt, grid_w=grid_w))
    while True:
        toks = next(lm)["tokens"]
        emb = 0.02 * rng.standard_normal(
            (batch, s_img, cfg.d_model)).astype(np.float32)
        yield {"tokens": toks, "patch_embeds": emb, "positions": pos}


def audio_stream(cfg: ModelConfig, batch: int, seq: int,
                 seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    from repro.models.frontends import delay_pattern
    rng = np.random.default_rng(seed)
    K = cfg.num_codebooks
    lm = lm_token_stream(cfg.vocab_size, batch, seq * K, seed)
    while True:
        toks = next(lm)["tokens"].reshape(batch, seq, K)
        delayed = np.asarray(delay_pattern(jnp.asarray(toks), K))
        emb = 0.02 * rng.standard_normal(
            (batch, seq, cfg.d_model)).astype(np.float32)
        yield {"frame_embeds": emb,
               "codebook_labels": delayed.astype(np.int32)}


def stream_for(cfg, batch: int, seq: int, seed: int = 0):
    """Family dispatch lives in the adapter registry (``repro.api``); this
    stays as the stable entry point over the raw stream constructors."""
    from repro.api.families import adapter_for  # lazy: api sits above data
    return adapter_for(cfg).stream(cfg, batch, seq, seed)


# ---------------------------------------------------------------------------
# prefetching + device placement (the paper's dedicated data thread)
# ---------------------------------------------------------------------------
BATCH_SPECS = {
    "tokens": ("batch", "seq"), "images": ("batch", None, None, None),
    "labels": ("batch",), "frames": ("batch", None), "senones": ("batch",),
    "patch_embeds": ("batch", "seq", "embed"),
    "positions": ("batch", "seq", None),
    "frame_embeds": ("batch", "seq", "embed"),
    "codebook_labels": ("batch", "seq", None),
}


_SENTINEL = object()    # queued when the source is exhausted: a finite
#                         source must end the consumer's iteration, not
#                         leave it blocked on an empty queue forever


class _SpanObserver:
    """A daemon thread that takes ``(recorder, t0, arrays)`` and records
    its span from ``t0`` to the moment every array is ready.  ``submit``
    never blocks.  The queue holds only batches whose copies are still
    pending, as many as the pipeline has in flight."""

    def __init__(self, kind: str):
        self.kind = kind
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._t = threading.Thread(target=self._run, name=kind, daemon=True)
        self._t.start()

    def submit(self, rec, t0: float, arrays) -> None:
        self._q.put((rec, t0, arrays))

    def _run(self) -> None:
        while True:
            rec, t0, arrays = self._q.get()
            try:
                jax.block_until_ready(arrays)
            except Exception:      # noqa: BLE001 — deleted or failed copy
                continue
            finally:
                del arrays
            rec.record_span(self.kind, t0, rec.clock())


_OBSERVERS: Dict[str, _SpanObserver] = {}
_OBSERVERS_LOCK = threading.Lock()


def _observer(kind: str) -> _SpanObserver:
    """The process's one observer of span ``kind``."""
    with _OBSERVERS_LOCK:
        if kind not in _OBSERVERS:
            _OBSERVERS[kind] = _SpanObserver(kind)
        return _OBSERVERS[kind]


class Prefetcher:
    """Background-thread prefetch with a bounded queue (double buffering).

    Finite sources terminate cleanly: exhaustion enqueues a sentinel that
    ``__next__`` turns into ``StopIteration``.  ``close()`` stops the
    worker, drains the queue and JOINS the thread (bounded), so no worker
    is left blocked on a full queue after the consumer goes away.  Each
    ``__next__`` is a ``data.wait`` span on ``recorder`` (the process
    recorder by default)."""

    def __init__(self, source: Iterator, depth: int = 2,
                 place: Optional[Callable] = None, recorder=None):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._rec = recorder if recorder is not None else process_recorder()
        self._place = place or make_placer(None, None, self._rec)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None

        def _put(item) -> bool:
            # bounded put that gives up when close() intervenes, so the
            # worker can never deadlock against a full queue
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for item in source:
                    if not _put(item):
                        return
            except BaseException as e:     # noqa: BLE001 — must cross threads
                # a crashed pipeline is NOT exhaustion: record the exception
                # so the consumer re-raises it instead of quietly stopping
                self._error = e
            finally:
                _put(_SENTINEL)

        self._t = threading.Thread(target=worker, daemon=True)
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        with self._rec.span("data.wait"):
            item = self._q.get()
            if item is _SENTINEL:
                try:                      # keep raising on subsequent calls
                    self._q.put_nowait(_SENTINEL)
                except queue.Full:
                    pass
                if self._error is not None:
                    raise self._error
                raise StopIteration
            return self._place(item)

    def close(self):
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._t.join(timeout=5.0)


def make_placer(mesh: Optional[Mesh], rules, recorder=None) -> Callable:
    """``place(batch)``: the batch on the devices, each field sharded over
    the mesh's batch axes (or on the default device when ``mesh`` is None).
    Each call is a ``data.place`` span on ``recorder`` (the process
    recorder by default); the observer threads close its ``data.h2d`` span
    when the copies have landed and, on a mesh of more than one device,
    its ``data.reshard`` span when the batch is spread over the mesh."""
    rec = recorder if recorder is not None else process_recorder()
    spread = mesh is not None and mesh.size > 1

    def shard(k, x):
        axes = BATCH_SPECS.get(k, ("batch",) + (None,) * (x.ndim - 1))
        return jax.device_put(x, rules.sharding(axes, x.shape, mesh))

    def place(batch):
        with rec.span("data.place") as span:
            landed = jax.tree.map(jnp.asarray, batch)
            t_spread = rec.clock() if spread and rec.enabled else None
            out = landed if mesh is None else {
                k: shard(k, x) for k, x in landed.items()}
        if rec.enabled:
            _observer("data.h2d").submit(rec, span.t0, landed)
            if spread:
                _observer("data.reshard").submit(rec, t_spread, out)
        return out
    return place
