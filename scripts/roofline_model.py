"""Analytic roofline model of the pretrain step on TPU v5e (no chip needed).

Reads the 49% MFU of `BENCH_TPU_CAPTURE.json` (97.31 TFLOP/s against the
197 TFLOP/s bf16 peak, one v5e chip, 2026-07-31) against a ceiling: a
per-layer FLOPs + HBM-traffic model of the exact compiled step (CIFAR-stem
ResNet-18 at batch 512, two views, NT-Xent, LARS), bounded per layer by

    t_layer >= max(FLOPs / peak FLOP/s, bytes / peak bytes/s)

with the v5e row of `simclr_tpu/obs/peaks.py` as the peaks.

Summing the bounds gives the fastest step the hardware allows for this
program; total-FLOPs / (bound * peak) is the best MFU any schedule could
reach. The model is deliberately OPTIMISTIC for the hardware (perfect
overlap, all elementwise fused into the convs, weights cached across the
batch, no padding/layout waste), so the resulting ceiling is a true upper
bound; XLA's actual 49% is then read against it.

Shapes come from the same tables the model uses (`models/arch.py`), so the
model tracks the zoo. Reference workload: /root/reference/model.py (f =
torchvision resnet18, CIFAR stem), batch 512/device, d=128.

Run: python scripts/roofline_model.py [--batch 512] [--arch resnet18]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from simclr_tpu.models.arch import (  # noqa: E402
    CONVS_PER_BLOCK,
    FEATURE_DIMS,
    STAGE_SIZES,
    STAGE_WIDTHS,
)
from simclr_tpu.obs.peaks import CHIP_PEAKS, V5E  # noqa: E402

PEAK = CHIP_PEAKS[V5E]
PEAK_TFLOPS = PEAK.bf16_flops_per_s
PEAK_HBM = PEAK.hbm_bytes_per_s
BF16 = 2
F32 = 4


def _ceil_to(x, m):
    return -(-x // m) * m


def mxu_eff(cout, contraction):
    """Fraction of the 128x128 MXU a matmul with these dims can fill.

    The systolic array processes 128 output lanes x 128 contraction lanes
    per pass; dims pad up to the tile. The model's single biggest
    refinement: ResNet-18's 64-wide stage-1 convs fill HALF the output
    lanes, and the 27-deep stem contraction fills ~21% of the depth.
    """
    return (cout / _ceil_to(cout, 128)) * (contraction / _ceil_to(contraction, 128))


def conv_ops(n, h, w, cin, cout, k, stride=1, input_grad=True):
    """One conv's (fwd FLOPs, fwd bytes, MXU eff) and the same for bwd.

    Traffic model (bf16 activations/weights): fwd reads in-act + weights,
    writes out-act. Backward = dgrad (read out-grad + weights, write
    in-grad) + wgrad (read in-act + out-grad, write weight grads in f32).
    BN/ReLU assumed fully fused (their FLOPs ignored, their traffic covered
    by the act reads/writes already counted) — optimistic for the hardware.
    Backward efficiency uses the dgrad dims (cin out-lanes, cout*k*k depth);
    wgrad is folded in at the same rate for simplicity.
    """
    ho, wo = h // stride, w // stride
    flops = 2 * n * ho * wo * cin * cout * k * k
    w_bytes = cin * cout * k * k * BF16
    in_b = n * h * w * cin * BF16
    out_b = n * ho * wo * cout * BF16
    fwd = (flops, in_b + w_bytes + out_b, mxu_eff(cout, cin * k * k))
    if input_grad:
        bwd = (
            2 * flops,
            (out_b + w_bytes + in_b) + (in_b + out_b + w_bytes * 2),
            mxu_eff(cin, cout * k * k),
        )
    else:
        # first layer: no gradient w.r.t. the images — wgrad only, whose
        # output lanes are cout and whose contraction is the huge N*H*W dim
        bwd = (flops, in_b + out_b + w_bytes * 2, mxu_eff(cout, n * h * w))
    return fwd, bwd


def augment_bytes(
    per_device_batch: int,
    impl: str = "xla",
    *,
    out_size: int = 32,
    height: int = 32,
    width: int = 32,
    channels: int = 3,
) -> int:
    """Analytic HBM bytes of the two-view augmentation per device-step.

    xla:   the vmapped per-view chain makes ~3 full passes over the batch
           (dequant+crop, jitter, grayscale/select), each reading uint8 or
           f32 and writing f32 intermediates — the measured ~2.2 ms row.
    fused: the Pallas kernel (simclr_tpu/ops/augment_pallas.py) reads each
           resident uint8 tile into VMEM ONCE and writes the two float32
           views — no per-stage HBM intermediates, so traffic collapses to
           the information-theoretic floor: one uint8 batch in, two f32
           views out (plus a negligible (n, 15) f32 parameter row stream,
           counted for honesty).

    Shared with scripts/augment_bench.py so the bench's "analytic HBM
    bytes" column and the live-MFU roofline can never disagree.
    """
    n = 2 * per_device_batch  # two views
    if impl == "fused":
        in_b = per_device_batch * height * width * channels  # uint8 read once
        out_b = n * out_size * out_size * channels * F32  # two f32 views
        params_b = n * 15 * F32  # per-view sampler rows streamed to VMEM
        return in_b + out_b + params_b
    return 3 * (n * height * width * channels * (1 + F32))


def model_step(
    arch: str, per_device_batch: int, d: int = 128, augment_impl: str = "xla"
):
    """Yield (name, flops, bytes) for every op of the full train step."""
    n = 2 * per_device_batch  # two views through the shared encoder
    ops = []

    def add(name, fwd, bwd):
        ops.append((name + " fwd", *fwd))
        ops.append((name + " bwd", *bwd))

    # CIFAR stem: 3x3 s1, no maxpool (reference model.py CIFAR surgery)
    add("stem 3x3 3-64 @32", *conv_ops(n, 32, 32, 3, 64, 3, input_grad=False))
    h = w = 32
    cin = 64
    convs = CONVS_PER_BLOCK[arch]
    for stage, blocks in enumerate(STAGE_SIZES[arch]):
        width = STAGE_WIDTHS[stage]
        cout = width if convs == 2 else width * 4
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            if convs == 2:  # BasicBlock: 3x3 + 3x3
                add(f"s{stage+1}b{b} 3x3 {cin}-{width} @{h}//{stride}",
                    *conv_ops(n, h, w, cin, width, 3, stride))
                add(f"s{stage+1}b{b} 3x3 {width}-{width}",
                    *conv_ops(n, h // stride, w // stride, width, width, 3))
            else:  # Bottleneck: 1x1 down, 3x3, 1x1 up
                add(f"s{stage+1}b{b} 1x1 {cin}-{width}",
                    *conv_ops(n, h, w, cin, width, 1))
                add(f"s{stage+1}b{b} 3x3 {width}-{width} //{stride}",
                    *conv_ops(n, h, w, width, width, 3, stride))
                add(f"s{stage+1}b{b} 1x1 {width}-{cout}",
                    *conv_ops(n, h // stride, w // stride, width, cout, 1))
            if b == 0 and (stage > 0 or convs == 3):
                add(f"s{stage+1} shortcut 1x1 {cin}-{cout}",
                    *conv_ops(n, h, w, cin, cout, 1, stride))
            if b == 0 and stage > 0:
                h, w = h // 2, w // 2
            cin = cout
    feat = FEATURE_DIMS[arch]

    def linear(name, n_, din, dout):
        fl = 2 * n_ * din * dout
        by = n_ * din * BF16 + din * dout * BF16 + n_ * dout * BF16
        add(name, (fl, by, mxu_eff(dout, din)),
            (2 * fl, 2 * by + din * dout * F32, mxu_eff(din, dout)))

    linear("head linear1", n, feat, feat)
    linear("head linear2", n, feat, d)
    # NT-Xent: z @ z.T similarity over the GLOBAL 2N candidates + softmax
    g = 2 * per_device_batch
    sim_fl = 2 * n * g * d
    sim_by = n * d * BF16 + g * d * BF16 + n * g * F32
    add("ntxent sim+softmax", (sim_fl, 3 * sim_by, mxu_eff(g, d)),
        (2 * sim_fl, 3 * sim_by, mxu_eff(d, g)))
    # augmentation: matmul-form RRC + jitter, measured ~2.2 ms r1 on the
    # xla path (~3 uint8/f32 passes over the raw batch); the fused Pallas
    # kernel collapses traffic to one uint8 read + two f32 view writes.
    # FLOPs are identical — both impls run the same crop/jitter math; only
    # the HBM bytes change. VPU work: eff n/a (1.0)
    aug_by = augment_bytes(per_device_batch, augment_impl)
    aug_name = f"augment (2 views, {augment_impl})"
    ops.append((aug_name, n * 32 * 32 * 3 * 40, aug_by, 1.0))
    # LARS + momentum: elementwise over ~11.5M params: read p,m,g (f32),
    # write p,m; plus the per-layer norm reductions (reads again)
    params = 11_498_048
    lars_by = params * F32 * 6
    ops.append(("LARS update", params * 12, lars_by, 1.0))
    return ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--per-layer", action="store_true")
    ap.add_argument(
        "--augment-impl", default="xla", choices=("xla", "fused"),
        help="augmentation pipeline the step runs (runtime.augment_impl): "
             "fused attributes the Pallas kernel's reclaimed HBM bandwidth",
    )
    args = ap.parse_args()

    ops = model_step(args.arch, args.batch, augment_impl=args.augment_impl)
    tot_fl = sum(o[1] for o in ops)
    tot_by = sum(o[2] for o in ops)
    naive_s = 0.0  # peak-MXU roofline (ignores tiling)
    bound_s = 0.0  # packing-aware roofline
    rows = []
    for name, fl, by, eff in ops:
        t_c = fl / (PEAK_TFLOPS * eff)
        t_m = by / PEAK_HBM
        t = max(t_c, t_m)
        naive_s += max(fl / PEAK_TFLOPS, t_m)
        bound_s += t
        rows.append((name, fl, by, eff, t * 1e3,
                     "compute" if t_c >= t_m else "memory"))
    if args.per_layer:
        print(f"{'op':42s} {'GFLOP':>8s} {'MB':>8s} {'MXUeff':>6s} "
              f"{'t_min ms':>9s} bound")
        for name, fl, by, eff, tms, kind in rows:
            print(f"{name:42s} {fl/1e9:8.2f} {by/1e6:8.1f} {eff:6.2f} "
                  f"{tms:9.4f} {kind}")
    crit_ai = PEAK_TFLOPS / PEAK_HBM
    print(f"\narch={args.arch} per-device batch={args.batch} "
          f"(2 views = {2*args.batch} images/step) "
          f"augment_impl={args.augment_impl}")
    if args.augment_impl == "fused":
        saved = augment_bytes(args.batch, "xla") - augment_bytes(args.batch, "fused")
        print(f"fused augmentation reclaims {saved/1e6:.2f} MB/step of HBM "
              f"traffic ({saved/PEAK_HBM*1e6:.1f} us at peak BW) vs xla")
    print(f"total: {tot_fl/1e12:.3f} TFLOP, {tot_by/1e9:.2f} GB "
          f"(program AI {tot_fl/tot_by:.0f} FLOP/B; critical AI "
          f"{crit_ai:.0f})")
    print(f"peak-MXU roofline (no tiling loss): {naive_s*1e3:.2f} ms "
          f"-> MFU ceiling {tot_fl/(naive_s*PEAK_TFLOPS)*100:.1f}%")
    # the trainer's imgs/sec counts DATASET images (batch pairs per step),
    # so the like-for-like ceiling is batch/bound, not 2*batch/bound
    print(f"packing-aware roofline: {bound_s*1e3:.2f} ms "
          f"-> max {args.batch/bound_s:,.0f} imgs/sec/chip "
          f"(dataset imgs; {2*args.batch/bound_s:,.0f} "
          f"view-imgs/sec); MFU ceiling "
          f"{tot_fl/(bound_s*PEAK_TFLOPS)*100:.1f}%")
    meas_ms = {512: 30.71}.get(args.batch)
    if meas_ms:
        print(f"measured (BENCH_TPU_CAPTURE.json, 2026-07-31): "
              f"{meas_ms:.2f} ms/step "
              f"({tot_fl/(meas_ms/1e3)/1e12:.1f} model-TFLOP/s) -> achieved "
              f"{bound_s*1e3/meas_ms*100:.0f}% of the packing-aware bound")


if __name__ == "__main__":
    main()
