"""In-band profiling stream — the paper's core contribution, in JAX.

SPRING threads a profiling stream *alongside* the data stream through a
streaming dataflow graph (paper §II.A, Listing 1):

  * each module reads the incoming profile stream and APPENDS its locally
    collected metric words to the end;
  * when the data stream SPLITS (clone), all profiling data follows the
    first output branch; every other branch starts a fresh stream holding a
    single PLACEHOLDER word;
  * when data streams MERGE, the first input's profile words are written to
    the output first, then the second's, and so on — deterministic order;
  * the label schema is STATICALLY predetermined, so the host (PS side)
    decodes the arriving flat word stream positionally.

Here the stream is a JAX pytree whose single dynamic leaf is a flat 1-D
``data`` vector of profile words, and whose static aux data is the label
schema.  Appending is functionally pure; the schema grows at *trace time*
(Python), satisfying the paper's own constraint that "the number of profiled
values per signal must be statically known".

Two collection policies mirror the paper:

  * ``inline``   — the faithful mechanism: the carried stream physically
                   grows (``jnp.concatenate``) through the layer stack.  Each
                   downstream module re-reads and re-writes every upstream
                   word — the O(L²) copy inefficiency the paper calls out in
                   §III.A ("repeatedly read and written by subsequent
                   layers").
  * ``shortcut`` — the paper's proposed optimization (§II.A, §IV future
                   work): sufficiently long streams bypass intermediate
                   modules straight to the final merge.  In JAX this is
                   realized with ``lax.scan`` ys / pre-laid-out buffers: each
                   layer emits a fixed-width record row directly into its
                   final resting place — O(L) copies.  See ``tape.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .codec import word_checksum, word_crc32

# Placeholder word written into the fresh stream of a non-primary split
# branch (paper: "the second output stream is initialized with a placeholder
# value").
PLACEHOLDER = -1.0

# Metric tag of the guard words appended by ``append_guarded``: a
# [sequence, checksum] pair per module record.
INTEGRITY_METRIC = "integrity"

_VALID_POLICIES = ("off", "inline", "shortcut")
_NON_SIGNAL_METRICS = ("placeholder", INTEGRITY_METRIC)

# Guard-word algorithms for ``append_guarded``.  ``xor24`` (default) emits a
# [seq, fold] pair; ``crc32`` emits [seq, lo16, hi16] — a full CRC-32 split
# into two sub-2**16 halves so it stays exact through a float32 stream.  The
# decoder tells them apart by the guard label's size, so streams built with
# either (or both) algorithms decode without any mode flag.
GUARD_ALGOS = ("xor24", "crc32")

# Host-side decode counters: ``reads`` counts device-to-host reads
# (``jax.device_get``), ``streams`` the streams decoded.
_STATS = {"reads": 0, "streams": 0}


def stream_stats() -> Dict[str, int]:
    """Decode counters: each ``decode`` / ``decode_verified`` adds one to
    ``streams`` and one to ``reads`` per device-to-host read it makes (the
    stream's words, then one per guard checksum it recomputes)."""
    return dict(_STATS)


def reset_stream_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _guard_words(payload, algo: str, dtype) -> jnp.ndarray:
    """The checksum words of a guard over ``payload``: ``[fold]`` for
    ``xor24``, ``[lo16, hi16]`` for ``crc32``, in the stream's ``dtype``.
    One compiled program per algorithm, payload size and dtype, shared by
    ``append_guarded`` and the verified decoder's recompute."""
    if algo == "crc32":
        check = word_crc32(payload)
    else:
        check = word_checksum(payload)[None]
    return check.astype(dtype)


def _read(x) -> np.ndarray:
    """One counted device-to-host read, as float64."""
    _STATS["reads"] += 1
    return np.asarray(jax.device_get(x), dtype=np.float64)


@dataclasses.dataclass(frozen=True)
class Label:
    """Semantic tag for a contiguous run of words in the profile stream.

    Mirrors the paper's "predetermined output profiling label list": the
    host decodes the flat stream purely positionally from these.
    """

    name: str            # e.g. "block3/moe/expert_fullness"
    metric: str          # e.g. "fifo_fullness", "act_rms", "placeholder"
    size: int            # number of words this label occupies

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"Label {self.name!r}: size must be >= 1")


def placeholder_label(branch: int) -> Label:
    return Label(name=f"__placeholder_b{branch}__", metric="placeholder", size=1)


@jax.tree_util.register_pytree_node_class
class ProfileStream:
    """A flat in-band stream of profile words with a static label schema."""

    __slots__ = ("data", "schema")

    def __init__(self, data: jnp.ndarray, schema: Tuple[Label, ...]):
        self.data = data
        self.schema = tuple(schema)

    # ------------------------------------------------------------------ #
    # pytree plumbing — ``data`` is the only dynamic leaf.
    # ------------------------------------------------------------------ #
    def tree_flatten(self):
        return (self.data,), self.schema

    @classmethod
    def tree_unflatten(cls, schema, children):
        (data,) = children
        return cls(data, schema)

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #
    @classmethod
    def create(cls, dtype=jnp.float32) -> "ProfileStream":
        """An empty stream (the profile input fed at the IP-core boundary)."""
        return cls(jnp.zeros((0,), dtype=dtype), ())

    @classmethod
    def placeholder(cls, dtype=jnp.float32, branch: int = 1) -> "ProfileStream":
        """Fresh stream for a non-primary split branch: one placeholder word."""
        return cls(
            jnp.full((1,), PLACEHOLDER, dtype=dtype),
            (placeholder_label(branch),),
        )

    # ------------------------------------------------------------------ #
    # properties
    # ------------------------------------------------------------------ #
    @property
    def dtype(self):
        return self.data.dtype

    @property
    def n_words(self) -> int:
        return int(sum(l.size for l in self.schema))

    @property
    def n_signals(self) -> int:
        """Number of non-placeholder labels (paper counts 'profiled signals').

        Guard words (``integrity`` labels) are framing, not signals.
        """
        return sum(1 for l in self.schema
                   if l.metric not in _NON_SIGNAL_METRICS)

    def __repr__(self):
        return (
            f"ProfileStream(words={self.n_words}, signals={self.n_signals}, "
            f"dtype={self.data.dtype})"
        )

    # ------------------------------------------------------------------ #
    # the three SPRING stream operations
    # ------------------------------------------------------------------ #
    def append(self, name: str, metric: str, values) -> "ProfileStream":
        """Module appends its locally collected words to the stream's end.

        ``values`` may be scalar or 1-D.  Gradients are stopped: profiling
        must not perturb the function being profiled (the in-band analogue
        of the paper's requirement that the profile path not corrupt the
        datapath — interference is studied separately in the simulator).
        """
        values = jnp.atleast_1d(jnp.asarray(values))
        if values.ndim != 1:
            values = values.reshape(-1)
        values = jax.lax.stop_gradient(values).astype(self.dtype)
        label = Label(name=name, metric=metric, size=int(values.shape[0]))
        return ProfileStream(
            jnp.concatenate([self.data, values]), self.schema + (label,)
        )

    def append_guarded(self, name: str, metric: str, values, *,
                       algo: str = "xor24") -> "ProfileStream":
        """``append`` plus a [sequence, checksum...] guard word group.

        The sequence number counts guarded records already in the stream, so
        the host detects dropped/duplicated/reordered module records; the
        checksum covers the payload words, so it detects in-band bit flips.
        The guard rides the stream as ordinary profile words — the exact
        in-band discipline the data words use (nothing out-of-band exists on
        the fabric).

        ``algo`` selects the checksum: ``"xor24"`` (default, one fold word)
        or ``"crc32"`` (two words, full CRC-32 — detects burst errors the
        fold can miss).  The guard label's size encodes the choice, so mixed
        streams decode without any side channel.
        """
        if algo not in GUARD_ALGOS:
            raise ValueError(f"algo must be one of {GUARD_ALGOS}, got {algo!r}")
        out = self.append(name, metric, values)
        payload = out.data[self.n_words:]
        seq = jnp.full((1,), float(self._next_seq()), dtype=self.dtype)
        check = _guard_words(payload, algo, self.dtype)
        guard = Label(name=f"{name}/__guard__", metric=INTEGRITY_METRIC,
                      size=1 + int(check.shape[0]))
        return ProfileStream(
            jnp.concatenate([out.data, seq, check]), out.schema + (guard,)
        )

    def _next_seq(self) -> int:
        return sum(1 for l in self.schema if l.metric == INTEGRITY_METRIC)

    def with_bitflip(self, word_index: int, bitmask: int = 1 << 17
                     ) -> "ProfileStream":
        """Fault injection: XOR ``bitmask`` into one word's bit pattern."""
        bits = jax.lax.bitcast_convert_type(
            self.data.astype(jnp.float32), jnp.uint32)
        bits = bits.at[word_index].set(
            bits[word_index] ^ jnp.uint32(bitmask))
        flipped = jax.lax.bitcast_convert_type(bits, jnp.float32)
        return ProfileStream(flipped.astype(self.dtype), self.schema)

    def truncated(self, n_words: int) -> "ProfileStream":
        """Fault injection: keep only the first ``n_words`` data words (a
        DMA transfer cut short); the schema still promises the full layout."""
        return ProfileStream(self.data[:n_words], self.schema)

    def split(self, n: int) -> Tuple["ProfileStream", ...]:
        """Stream split in synchrony with a data-stream split (clone).

        Branch 0 carries all existing profile words; branches 1..n-1 are
        initialized with a placeholder word each (paper §II.A).
        """
        if n < 1:
            raise ValueError("split requires n >= 1")
        out = [self]
        for b in range(1, n):
            out.append(ProfileStream.placeholder(dtype=self.dtype, branch=b))
        return tuple(out)

    @staticmethod
    def merge(*streams: "ProfileStream") -> "ProfileStream":
        """Stream merge in synchrony with a data merge: input 0 first, then 1…"""
        if not streams:
            raise ValueError("merge requires at least one stream")
        dtype = streams[0].dtype
        data = jnp.concatenate([s.data.astype(dtype) for s in streams])
        schema: Tuple[Label, ...] = ()
        for s in streams:
            schema = schema + s.schema
        return ProfileStream(data, schema)

    # ------------------------------------------------------------------ #
    # host-side (PS-side) decode
    # ------------------------------------------------------------------ #
    def label_list(self) -> Tuple[Label, ...]:
        """The predetermined output profiling label list."""
        return self.schema

    def decode(self) -> Dict[str, np.ndarray]:
        """Positional decode of the flat word stream into {label: values}.

        Runs host-side on concrete arrays (the PS-side interpretation step).
        Placeholder words are dropped, like the paper's post-processing.
        """
        _STATS["streams"] += 1
        arr = _read(self.data)
        out: Dict[str, np.ndarray] = {}
        cursor = 0
        for label in self.schema:
            words = arr[cursor : cursor + label.size]
            cursor += label.size
            if label.metric == "placeholder":
                continue
            if label.name in out:  # same site profiled twice (e.g. two steps)
                out[label.name] = np.concatenate([out[label.name], words])
            else:
                out[label.name] = words
        if cursor != arr.shape[0]:
            raise ValueError(
                f"schema covers {cursor} words but stream has {arr.shape[0]}"
            )
        return out

    def decode_verified(self) -> Tuple[Dict[str, np.ndarray], "IntegrityReport"]:
        """Fault-tolerant positional decode with per-record verification.

        Unlike ``decode`` this never raises on a damaged stream: corrupted
        records (checksum mismatch) are quarantined, records lost to a
        truncated transfer are reported missing, sequence-number gaps are
        flagged, and every intact signal is returned as usual.
        """
        _STATS["streams"] += 1
        arr = _read(self.data)
        n = arr.shape[0]
        out: Dict[str, np.ndarray] = {}
        status: Dict[str, str] = {}
        quarantined: List[str] = []
        missing: List[str] = []
        seq_errors: List[str] = []
        seen_seq: List[int] = []
        cursor = 0
        pending: Optional[Tuple[str, np.ndarray]] = None  # awaiting guard

        def commit(name: str, words: np.ndarray, ok: bool):
            if ok:
                if name in out:
                    out[name] = np.concatenate([out[name], words])
                else:
                    out[name] = words
                status[name] = "ok" if status.get(name) != "corrupt" else "corrupt"
            else:
                quarantined.append(name)
                status[name] = "corrupt"
                out.pop(name, None)

        for label in self.schema:
            lo, hi = cursor, cursor + label.size
            cursor = hi
            if hi > n:  # transfer cut short: the record never fully arrived
                if label.metric not in _NON_SIGNAL_METRICS:
                    missing.append(label.name)
                    status[label.name] = "missing"
                elif label.metric == INTEGRITY_METRIC and pending is not None:
                    # payload arrived but its guard didn't: keep, unverified
                    commit(*pending, ok=True)
                    status[pending[0]] = "unverified"
                    pending = None
                continue
            words = arr[lo:hi]
            if label.metric == "placeholder":
                continue
            if label.metric == INTEGRITY_METRIC:
                if pending is None:
                    seq_errors.append(f"orphan guard {label.name}")
                    continue
                name, payload = pending
                pending = None
                # [seq, lo16, hi16] is a crc32 guard, [seq, fold] an xor24
                algo = "crc32" if label.size >= 3 else "xor24"
                expect = _read(_guard_words(payload, algo, self.dtype))
                ok = np.array_equal(words[1:1 + expect.shape[0]], expect)
                commit(name, payload, ok=bool(ok))
                seq = float(words[0])
                if np.isfinite(seq) and 0 <= seq < 2**31:
                    seen_seq.append(int(seq))
                else:  # corrupted framing word — never crash the decoder
                    seq_errors.append(f"unreadable sequence word for {name}")
                continue
            if pending is not None:  # previous payload had no guard
                commit(*pending, ok=True)
                status[pending[0]] = "unverified"
                pending = None
            pending = (label.name, words)
        if pending is not None:  # trailing unguarded record
            commit(*pending, ok=True)
            status[pending[0]] = "unverified"
        # guarded records must count up by 1; a restart at 0 is a legitimate
        # split-branch boundary, anything else is a gap/dup/reorder
        for a, b in zip(seen_seq, seen_seq[1:]):
            if b != a + 1 and b != 0:
                seq_errors.append(f"sequence break {a}->{b} in {seen_seq}")
                break
        report = IntegrityReport(
            n_words_expected=self.n_words, n_words_received=n,
            status=status, quarantined=sorted(set(quarantined)),
            missing=missing, seq_errors=seq_errors,
            truncated=(n < self.n_words), surplus=max(0, n - self.n_words))
        return out, report


@dataclasses.dataclass
class IntegrityReport:
    """Host-side verdict on one decoded profile stream."""

    n_words_expected: int
    n_words_received: int
    status: Dict[str, str]          # signal -> ok | unverified | corrupt | missing
    quarantined: List[str]
    missing: List[str]
    seq_errors: List[str]
    truncated: bool
    surplus: int

    @property
    def ok(self) -> bool:
        return (not self.quarantined and not self.missing
                and not self.seq_errors and not self.truncated
                and self.surplus == 0)

    @property
    def n_corrupt(self) -> int:
        return len(self.quarantined)

    def summary(self) -> str:
        if self.ok:
            return (f"stream intact: {self.n_words_received} words, "
                    f"{len(self.status)} signal(s) verified")
        bits = [f"words {self.n_words_received}/{self.n_words_expected}"]
        if self.quarantined:
            bits.append(f"quarantined: {', '.join(self.quarantined)}")
        if self.missing:
            bits.append(f"missing: {', '.join(self.missing)}")
        if self.seq_errors:
            bits.append("; ".join(self.seq_errors))
        if self.surplus:
            bits.append(f"{self.surplus} surplus word(s)")
        return "stream damaged: " + " | ".join(bits)

    def __str__(self) -> str:
        return self.summary()


def validate_policy(policy: str) -> str:
    if policy not in _VALID_POLICIES:
        raise ValueError(f"policy must be one of {_VALID_POLICIES}, got {policy!r}")
    return policy
