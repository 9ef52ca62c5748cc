"""Windowed feature aggregation at scale — the bandwidth-bound engine.

Scalar vertex programs (PageRank, CC) move 4 bytes per edge endpoint, so at
any scale their superstep is bound by the accelerator's per-element
random-access rate — the one primitive graph workloads can't tile. This
engine propagates F-WIDE feature rows instead (GNN-style mean aggregation
over the temporal window): every memory access becomes a 128-lane row-tile
move, which the TPU executes at HBM bandwidth. It is the "embedding /
representation over a temporal window" workload class the reference cannot
express at all (its analysers push scalars through actor mailboxes —
``Analyser.scala:30-63``), and the scale benchmark where the chip, not the
host, sets the ceiling.

Design:
* operates on a ``DeviceSweep``'s resident fold state — the window mask
  ``alive ∧ latest ≥ T − W`` (``Entity.scala:193-201`` semantics) is
  computed on device, nothing ships per hop;
* the edge axis is processed in fixed chunks under one ``lax.scan`` so the
  [m, F] payload never materialises (HBM holds 2 chunk tiles, not 50 GB);
* aggregation is sum + degree-normalise (mean), the GraphSAGE-mean shape;
  ``self_weight`` mixes each vertex's own features back in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .device_sweep import DeviceSweep


@functools.lru_cache(maxsize=64)
def _compiled_propagate(n_pad: int, m_pad: int, chunk: int, F: int,
                        rounds: int, self_weight: float, tdt: str,
                        fdt: str = "float32"):
    """``fdt`` is the feature STORAGE dtype: bfloat16 halves the HBM bytes
    of the per-edge row gathers (the term this engine is bound by on TPU)
    while accumulation, degree-normalise and the L2 norm stay float32 —
    the standard mixed-precision aggregation recipe."""
    tdt = jnp.dtype(tdt)
    fdt = jnp.dtype(fdt)
    C = m_pad // chunk

    def propagate(X, e_src, e_dst, e_lat, e_alive, time, window):
        X = X.astype(fdt)
        info = jnp.iinfo(tdt)
        lo = jnp.clip(time - window, info.min, info.max).astype(tdt)
        mask = e_alive & ((window < 0) | (e_lat >= lo))   # [m_pad]
        src_c = e_src.reshape(C, chunk)
        dst_c = e_dst.reshape(C, chunk)
        msk_c = mask.reshape(C, chunk)
        ones = jnp.ones((chunk,), jnp.float32)

        # masked in-degree is round-invariant — one per-element pass
        # total, not one per round
        def deg_body(deg, ins):
            d, mk = ins
            return deg + jax.ops.segment_sum(
                jnp.where(mk, ones, 0.0), d, num_segments=n_pad,
                indices_are_sorted=True), None

        deg, _ = jax.lax.scan(deg_body,
                              jnp.zeros((n_pad,), jnp.float32),
                              (dst_c, msk_c))
        inv_deg = 1.0 / jnp.maximum(deg, 1.0)

        def one_round(H, _):
            def chunk_body(agg, ins):
                s, d, mk = ins
                # gather reads fdt rows from HBM; the f32 convert
                # happens in-flight, so bf16 storage halves the
                # streamed bytes
                G = jnp.where(mk[:, None], H[s, :].astype(jnp.float32),
                              0.0)
                return agg + jax.ops.segment_sum(
                    G, d, num_segments=n_pad,
                    indices_are_sorted=True), None

            agg, _ = jax.lax.scan(
                chunk_body, jnp.zeros((n_pad, F), jnp.float32),
                (src_c, dst_c, msk_c))
            H2 = agg * inv_deg[:, None]
            H2 = self_weight * H.astype(jnp.float32) \
                + (1.0 - self_weight) * H2
            # row L2 normalise keeps magnitudes bounded across rounds
            norm = jnp.sqrt(jnp.sum(H2 * H2, axis=1, keepdims=True))
            return (H2 / jnp.maximum(norm, 1e-12)).astype(fdt), None

        H, _ = jax.lax.scan(one_round, X, None, length=rounds)
        return H

    return jax.jit(propagate)


class FeatureAggregator:
    """GNN-style windowed mean aggregation over a device-resident sweep.

    ``propagate(X, T, window, rounds)`` advances the sweep to T and returns
    the propagated [n_pad, F] features (async device array). Rows are the
    sweep's global dense vertex space (``ds.uv``)."""

    def __init__(self, ds: DeviceSweep, feature_dim: int = 128,
                 chunk: int = 1 << 22, self_weight: float = 0.5,
                 dtype: str = "float32"):
        self.ds = ds
        self.F = feature_dim
        # chunk must divide m_pad; shrink to m_pad when the graph is small
        self.chunk = min(chunk, ds.m_pad)
        while ds.m_pad % self.chunk:
            self.chunk //= 2
        self.self_weight = float(self_weight)
        # feature storage dtype: "bfloat16" halves the HBM-bound row
        # traffic on TPU; accumulation stays float32 (_compiled_propagate)
        self.dtype = jnp.dtype(dtype)

    def random_features(self, seed: int = 0):
        """Deterministic on-device init (unit-norm rows) — no host transfer."""
        X = jax.random.normal(jax.random.PRNGKey(seed),
                              (self.ds.n_pad, self.F), jnp.float32)
        return (X / jnp.linalg.norm(X, axis=1, keepdims=True)) \
            .astype(self.dtype)

    def propagate(self, X, time: int | None = None, *,
                  window: int | None = None, rounds: int = 2):
        ds = self.ds
        if time is not None:
            ds.advance(time)
        if ds.t_now is None:
            raise ValueError("advance the sweep (or pass time=) first")
        fn = _compiled_propagate(
            ds.n_pad, ds.m_pad, self.chunk, self.F, int(rounds),
            self.self_weight, np.dtype(ds.tdtype).name, self.dtype.name)
        v_lat, v_alive, v_first, e_lat, e_alive, e_first = ds._bufs
        return fn(X, ds.e_src, ds.e_dst, e_lat, e_alive,
                  jnp.asarray(ds.t_now, jnp.int64),
                  jnp.asarray(-1 if window is None else int(window),
                              jnp.int64))

    def traffic_bytes(self, rounds: int) -> int:
        """Approximate HBM bytes per propagate call (for utilisation
        reporting): per round, the edge axis streams a gathered F-row and
        writes it once into the accumulator, plus index/mask columns; the
        masked-degree pass runs ONCE per call (round-invariant)."""
        fb = self.dtype.itemsize                # feature storage bytes/lane
        per_edge = self.F * (fb + 4) + 2 * 4 + 1  # fdt gather + f32 scatter
        per_vertex = self.F * (2 * 4 + fb)      # f32 acc read+write, fdt H
        deg_pass = self.ds.m_pad * (4 + 1)      # dst ids + mask, one pass
        return deg_pass + rounds * (self.ds.m_pad * per_edge
                                    + self.ds.n_pad * per_vertex)

    def flops(self, rounds: int) -> int:
        """Adds/multiplies per propagate call (mean-aggregate + mix + norm)."""
        return rounds * (self.ds.m_pad * self.F          # segment adds
                         + self.ds.n_pad * self.F * 6)   # mean/mix/normalise
