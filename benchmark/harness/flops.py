"""Operation counts of the video UNet from the configuration's shapes.

Counts the WORK (multiply-adds x 2 of every convolution, dense layer and
attention product the architecture defines), never the implementation: no
padding, no recompute, and a text key/value projection once per batch
element (the program repeats it per frame). Norms, softmax and activations
are not counted.

``unet_ops(cfg, frames, latent, text_len)`` returns one record per product:
  site, fwd, act_operands_with_grad (how many of the product's operands
  carry a gradient in the tuning step), weight_grad (the product's weight is
  a trainable leaf).
The tuning step's count is forward + one forward-sized product per operand
that carries a gradient + one per trainable weight: activation gradients
only where gradient flows (nothing upstream of the first trainable leaf, no
text side), weight gradients of the trainable leaves only, and remat
recompute NOT counted (MFU counts useful work).
"""

from __future__ import annotations

TRAINABLE = ("attn1.to_q", "attn2.to_q", "attn_temp")


class _Walk:
    def __init__(self, frames: int, trainable):
        self.f = frames
        self.ops = []
        self.grad = False       # does the running activation carry gradient
        self.trainable = trainable

    def _is_trainable(self, site: str) -> bool:
        toks = site.split(".")
        for pat in self.trainable:
            p = pat.split(".")
            if any(toks[i:i + len(p)] == p
                   for i in range(len(toks) - len(p) + 1)):
                return True
        return False

    def param(self, site: str, fwd: float, *, in_grad=None) -> None:
        """A product of an activation with a weight."""
        main_path = in_grad is None  # a side input never changes the flag
        in_grad = self.grad if main_path else in_grad
        tr = self._is_trainable(site)
        self.ops.append({"site": site, "fwd": fwd,
                         "act_operands_with_grad": 1 if in_grad else 0,
                         "weight_grad": tr})
        if main_path:
            self.grad = self.grad or tr

    def act2(self, site: str, fwd: float, grads: int) -> None:
        """A product of two activations (attention)."""
        self.ops.append({"site": site, "fwd": fwd,
                         "act_operands_with_grad": grads,
                         "weight_grad": False})


def _resnet(w: _Walk, site: str, cin: int, cout: int, r: int, temb: int):
    n = r * r * w.f
    w.param(f"{site}.conv1", 2.0 * n * 9 * cin * cout)
    w.param(f"{site}.time_emb_proj", 2.0 * temb * cout, in_grad=False)
    w.param(f"{site}.conv2", 2.0 * n * 9 * cout * cout)
    if cin != cout:
        w.param(f"{site}.conv_shortcut", 2.0 * n * cin * cout)


def _transformer(w: _Walk, site: str, c: int, r: int, text_len: int,
                 text_dim: int, depth: int = 1):
    n, f = r * r, w.f
    w.param(f"{site}.proj_in", 2.0 * f * n * c * c)
    for d in range(depth):
        b = f"{site}.blocks_{d}"
        kv_grad = w.grad  # frame 0's K/V input carries what x carries
        w.param(f"{b}.attn1.to_q", 2.0 * f * n * c * c)
        w.param(f"{b}.attn1.to_k", 2.0 * n * c * c, in_grad=kv_grad)
        w.param(f"{b}.attn1.to_v", 2.0 * n * c * c, in_grad=kv_grad)
        g = 2 if kv_grad else 1
        w.act2(f"{b}.attn1.qk", 2.0 * f * n * n * c, g)
        w.act2(f"{b}.attn1.pv", 2.0 * f * n * n * c, g)
        w.param(f"{b}.attn1.to_out", 2.0 * f * n * c * c)
        w.param(f"{b}.attn2.to_q", 2.0 * f * n * c * c)
        w.param(f"{b}.attn2.to_k", 2.0 * text_len * text_dim * c,
                in_grad=False)
        w.param(f"{b}.attn2.to_v", 2.0 * text_len * text_dim * c,
                in_grad=False)
        w.act2(f"{b}.attn2.qk", 2.0 * f * n * text_len * c, 1)
        w.act2(f"{b}.attn2.pv", 2.0 * f * n * text_len * c, 1)
        w.param(f"{b}.attn2.to_out", 2.0 * f * n * c * c)
        w.param(f"{b}.ff.proj_geglu", 2.0 * f * n * c * 8 * c)
        w.param(f"{b}.ff.proj_out", 2.0 * f * n * 4 * c * c)
        for p in ("to_q", "to_k", "to_v"):
            w.param(f"{b}.attn_temp.{p}", 2.0 * f * n * c * c)
        w.act2(f"{b}.attn_temp.qk", 2.0 * n * f * f * c, 2)
        w.act2(f"{b}.attn_temp.pv", 2.0 * n * f * f * c, 2)
        w.param(f"{b}.attn_temp.to_out", 2.0 * f * n * c * c)
    w.param(f"{site}.proj_out", 2.0 * f * n * c * c)


def unet_ops(cfg: dict, frames: int, latent: int, text_len: int,
             trainable=TRAINABLE) -> list:
    """One batch element's forward, product by product. ``cfg`` holds the
    published ``unet/config.json`` keys of the configuration's file."""
    ch = list(cfg["block_out_channels"])
    layers = int(cfg["layers_per_block"])
    down, up = cfg["down_block_types"], cfg["up_block_types"]
    text_dim, cin0, cout0 = (cfg["cross_attention_dim"], cfg["in_channels"],
                             cfg["out_channels"])
    temb = ch[0] * 4
    w = _Walk(frames, trainable)
    w.param("time_embedding.linear_1", 2.0 * ch[0] * temb, in_grad=False)
    w.param("time_embedding.linear_2", 2.0 * temb * temb, in_grad=False)
    r = latent
    w.param("conv_in", 2.0 * frames * r * r * 9 * cin0 * ch[0])
    skips, c = [ch[0]], ch[0]
    for i, kind in enumerate(down):
        for j in range(layers):
            _resnet(w, f"down_blocks_{i}.resnets_{j}", c, ch[i], r, temb)
            c = ch[i]
            if kind.startswith("CrossAttn"):
                _transformer(w, f"down_blocks_{i}.attentions_{j}", c, r,
                             text_len, text_dim)
            skips.append(c)
        if i < len(ch) - 1:
            r //= 2
            w.param(f"down_blocks_{i}.downsample",
                    2.0 * frames * r * r * 9 * c * c)
            skips.append(c)
    _resnet(w, "mid_block.resnets_0", c, c, r, temb)
    _transformer(w, "mid_block.attentions_0", c, r, text_len, text_dim)
    _resnet(w, "mid_block.resnets_1", c, c, r, temb)
    rev = list(reversed(ch))
    for i, kind in enumerate(up):
        for j in range(layers + 1):
            _resnet(w, f"up_blocks_{i}.resnets_{j}", c + skips.pop(), rev[i],
                    r, temb)
            c = rev[i]
            if kind.startswith("CrossAttn"):
                _transformer(w, f"up_blocks_{i}.attentions_{j}", c, r,
                             text_len, text_dim)
        if i < len(ch) - 1:
            r *= 2
            w.param(f"up_blocks_{i}.upsample",
                    2.0 * frames * r * r * 9 * c * c)
    w.param("conv_out", 2.0 * frames * r * r * 9 * c * cout0)
    assert not skips and r == latent
    return w.ops


def forward_flops(ops: list) -> float:
    return sum(o["fwd"] for o in ops)


def tune_step_flops(ops: list) -> float:
    return sum(o["fwd"] * (1 + o["act_operands_with_grad"]
                           + (1 if o["weight_grad"] else 0)) for o in ops)


def attention_sites(cfg: dict, latent: int) -> list:
    """``[(resolution, channels)]`` of every transformer block of one
    forward, in order — the frame-attention call sites."""
    ch = list(cfg["block_out_channels"])
    layers = int(cfg["layers_per_block"])
    sites, r = [], latent
    for i, kind in enumerate(cfg["down_block_types"]):
        if kind.startswith("CrossAttn"):
            sites += [(r, ch[i])] * layers
        if i < len(ch) - 1:
            r //= 2
    sites.append((r, ch[-1]))
    for i, kind in enumerate(cfg["up_block_types"]):
        if kind.startswith("CrossAttn"):
            sites += [(r, list(reversed(ch))[i])] * (layers + 1)
        if i < len(ch) - 1:
            r *= 2
    return sites
