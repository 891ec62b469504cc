"""Operations and bytes of a DeepSeek-V3-style decoder (MLA attention, a
dense FFN in the leading layers, routed and shared experts after them) at
one chip's expert-parallel share, and the decode step's share of its
roofline from a trace.

As in ``flops.py``, these count what the algorithm must do: a decode step
reads every weight outside the routed experts once (at the dtype the
program keeps it: bfloat16, the router float32), and of the held experts
only those the step's routing counter shows were hit; it reads the live
tokens' latent (c_kv and the 64-wide rotary key, 1,152 bytes a token a
layer) once and writes the new tokens' latent once.  Its FLOPs are those
of the live rows through every non-expert matmul (absorbed MLA: q_eff and
the W_uv product are kv_up's FLOPs), of the routed (token, expert) pairs
only, and of latent attention over the live tokens.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import flops as F
from . import program as PG
from . import trace as TR

KERNEL = "paged_mla_decode_attention"
DECODE = "jit_serve_decode"


@dataclass(frozen=True)
class MlaMoe:
    layers: int
    dense_layers: int
    d: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    d_ff: int
    d_expert: int
    router: int          # the router's width: every expert
    held: int            # experts held here
    shared: int
    vocab: int

    @classmethod
    def of(cls, c: Dict[str, Any]) -> "MlaMoe":
        return cls(layers=int(c["num_hidden_layers"]),
                   dense_layers=int(c["first_k_dense_replace"]),
                   d=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   q_rank=int(c["q_lora_rank"]),
                   kv_rank=int(c["kv_lora_rank"]),
                   nope=int(c["qk_nope_head_dim"]),
                   rope=int(c["qk_rope_head_dim"]),
                   v=int(c["v_head_dim"]),
                   d_ff=int(c["intermediate_size"]),
                   d_expert=int(c["moe_intermediate_size"]),
                   router=int(c["router_experts"]),
                   held=int(c["n_routed_experts"]),
                   shared=int(c["n_shared_experts"]),
                   vocab=int(c["vocab_size"]))

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    # -- parameters ----------------------------------------------------------
    @property
    def mla_matmul_params(self) -> int:
        H = self.heads
        return (self.d * self.q_rank
                + self.q_rank * H * (self.nope + self.rope)
                + self.d * (self.kv_rank + self.rope)
                + self.kv_rank * H * (self.nope + self.v)
                + H * self.v * self.d)

    @property
    def mla_params(self) -> int:
        return self.mla_matmul_params + self.q_rank + self.kv_rank

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.d_expert

    @property
    def shared_params(self) -> int:
        return self.shared * self.expert_params

    @property
    def router_params(self) -> int:
        return self.d * self.router + self.router

    @property
    def params(self) -> int:
        """Every parameter held here."""
        per_layer = self.mla_params + 2 * self.d
        dense = 3 * self.d * self.d_ff
        moe = (self.held * self.expert_params + self.shared_params
               + self.router_params)
        return (self.layers * per_layer + self.dense_layers * dense
                + self.moe_layers * moe + 2 * self.vocab * self.d + self.d)

    def fixed_bytes(self, itemsize: int = 2) -> float:
        """Bytes of every weight outside the routed experts (the router in
        float32, the rest at ``itemsize``)."""
        router = self.moe_layers * self.router_params
        rest = self.params - router - self.moe_layers * self.held \
            * self.expert_params
        return float(rest * itemsize + router * 4)

    def fixed_matmul_params(self) -> int:
        """Parameters a decode token multiplies outside the routed
        experts."""
        return (self.layers * self.mla_matmul_params
                + self.dense_layers * 3 * self.d * self.d_ff
                + self.moe_layers * (self.shared_params
                                     + self.d * self.router)
                + self.vocab * self.d)

    def latent_bytes_per_token(self, itemsize: int = 2) -> int:
        """c_kv and the rotary key of one token in one layer."""
        return (self.kv_rank + self.rope) * itemsize

    # -- one decode step ---------------------------------------------------
    def decode_step(self, rows: int, kv_tokens: int,
                    routed: Sequence[Sequence[int]], itemsize: int = 2
                    ) -> Dict[str, float]:
        """``rows`` live requests whose caches hold ``kv_tokens`` tokens in
        all (each row's new token included); ``routed`` [L_moe][E_held]
        the step's tokens routed to each held expert."""
        hit = sum(1 for layer in routed for n in layer if n > 0)
        pairs = sum(int(n) for layer in routed for n in layer)
        lat = self.latent_bytes_per_token(itemsize)
        flops = (2 * rows * self.fixed_matmul_params()
                 + 2 * pairs * self.expert_params
                 + self.attention_flops(kv_tokens))
        byts = (self.fixed_bytes(itemsize)
                + hit * self.expert_params * itemsize
                + self.layers * (kv_tokens + rows) * lat)
        return {"flops": float(flops), "bytes": float(byts)}

    def attention_flops(self, kv_tokens: int) -> float:
        """Latent scores (c_kv and rotary parts) and values over the live
        tokens, every layer and head."""
        return float(self.layers * 2 * self.heads
                     * (2 * self.kv_rank + self.rope) * kv_tokens)

    def paged_mla_kernel(self, rows: int, kv_tokens: int,
                         itemsize: int = 2) -> Dict[str, float]:
        """The MLA decode kernel over all layers of one step: the live
        latent read once, each live row's q_eff, q_rope and output moved
        once; scores and values over the live tokens."""
        H = self.heads
        q_out = rows * H * (2 * self.kv_rank + self.rope) * itemsize
        byts = self.layers * (kv_tokens * self.latent_bytes_per_token(
            itemsize) + q_out)
        return {"flops": self.attention_flops(kv_tokens),
                "bytes": float(byts)}


def per_step(ctx: Dict[str, Any]) -> List[Tuple[float, float, int, int,
                                                 Any]]:
    """For each traced decode tick that ran the decode program: (device ns
    of ``jit_serve_decode``, device ns of its ``paged_mla_decode_attention``
    ops, live rows, live tokens, the step's routing counter).  The ticks'
    host spans pair, in order, with what the driver recorded around
    each."""
    tr = ctx["trace"]
    dev = ctx["devices"][0]
    spans = [s for s in tr.spans if s.name == "bench.decode_tick"]
    ticks = ctx.get("decode_ticks", [])
    routed = ctx.get("decode_routed", [])
    mods = PG.executions(tr, dev, (DECODE,))
    kern = [e for e in TR.kernel_events(tr, dev)
            if TR.op_parts(e.name)[0] == KERNEL
            and PG.program_name(e.module) == DECODE]
    out = []
    for sp, (rows, kv), r in zip(spans, ticks, routed):
        if rows == 0 or r is None:
            continue
        m_ns = sum(e.dur for e in mods if sp.start <= e.start < sp.end)
        k_ns = sum(e.dur for e in kern if sp.start <= e.start < sp.end)
        if m_ns > 0:
            out.append((m_ns, k_ns, rows, kv, r))
    return out


def decode_share(ctx: Dict[str, Any], kernel: bool) -> Optional[float]:
    """Share (%) of the roofline: the least time the traced decode steps
    (or their MLA kernel calls) need at the chip's peaks, over the device
    time they took.  None where the trace holds no such step or kernel."""
    if "router_experts" not in ctx.get("config", {}):
        return None
    model = MlaMoe.of(ctx["config"])
    need = took = 0.0
    for m_ns, k_ns, rows, kv, r in per_step(ctx):
        t = k_ns if kernel else m_ns
        if t <= 0:
            continue
        work = (model.paged_mla_kernel(rows, kv) if kernel
                else model.decode_step(rows, kv, r))
        need += F.roofline_s(work, ctx["peak"])
        took += t / 1e9
    if took <= 0:
        return None
    return 100.0 * need / took
