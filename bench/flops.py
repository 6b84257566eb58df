"""Operations and bytes from shapes: the U-Net forward's multiply-adds
(convolutions, dense layers and attention products, two FLOPs each;
normalisations and activations are left out) and the bytes the fused DDPM
step kernel moves.  A convolution counts only the taps that fall inside
the image: the products with "SAME" padding's zeros are no work the model
needs (XLA's cost analysis counts them the same way)."""
from __future__ import annotations


def _taps(n: int, k: int, stride: int) -> int:
    """Kernel taps inside the input, summed over one axis's outputs, for a
    "SAME"-padded convolution."""
    m = -(-n // stride)
    lo = max((m - 1) * stride + k - n, 0) // 2
    return sum(1 for o in range(m) for j in range(k)
               if 0 <= o * stride - lo + j < n)


def unet_forward_flops(cfg: dict) -> int:
    """FLOPs of one image's forward pass at ``cfg``."""
    td, nc = cfg["time_dim"], cfg["n_classes"]
    ws = [cfg["base_width"] * m for m in cfg["width_mults"]]
    flops = 2 * (2 * td * td) + 2 * nc * td

    def conv(res, k, cin, cout, stride=1):
        return 2 * _taps(res, k, stride) ** 2 * cin * cout

    def res_block(res, cin, cout):
        f = conv(res, 3, cin, cout) + conv(res, 3, cout, cout) + 2 * td * cout
        if cin != cout:
            f += conv(res, 1, cin, cout)
        return f

    def attn(res, c):
        n = res * res
        return 4 * 2 * n * c * c + 2 * 2 * n * n * c

    res = cfg["image_size"]
    flops += conv(res, 3, cfg["channels"], ws[0])
    skips = [ws[0]]
    cin = ws[0]
    for i, c in enumerate(ws):
        for _ in range(cfg["n_res_blocks"]):
            flops += res_block(res, cin, c)
            if res in cfg["attn_resolutions"]:
                flops += attn(res, c)
            cin = c
            skips.append(c)
        if i < len(ws) - 1:
            flops += conv(res, 3, c, c, stride=2)
            res = -(-res // 2)
            skips.append(c)
    flops += 2 * res_block(res, cin, cin) + attn(res, cin)
    for i, c in reversed(list(enumerate(ws))):
        for _ in range(cfg["n_res_blocks"] + 1):
            flops += res_block(res, cin + skips.pop(), c)
            if res in cfg["attn_resolutions"]:
                flops += attn(res, c)
            cin = c
        if i > 0:
            res *= 2
            flops += conv(res, 3, c, c)
    return flops + conv(res, 3, ws[0], cfg["channels"])


LANES, F32 = 128, 4


def ddpm_step_bytes(rows: int, images: int, cfg: dict) -> int:
    """Bytes one launch of the batched DDPM step kernel reads and writes:
    x, eps and noise in, x out, float32, each of ``rows`` slabs of
    ``images`` images padded to whole 128-lane rows."""
    per = images * cfg["image_size"] ** 2 * cfg["channels"]
    padded = -(-per // LANES) * LANES
    return 4 * rows * padded * F32
