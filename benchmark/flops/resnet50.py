"""Analytic training FLOPs of resnet50 per image: what the forward and
backward passes require, 2 FLOPs per multiply-add, backward = 2 x forward,
recomputed operations not counted. Convolutions and the linear layer only;
batch norm, ReLU, pooling and the optimizer are under 1%.

bench.py's constant for the same model is 12.3 GFLOP per image: it takes the
4.09 G multiply-adds of the forward pass for FLOPs (while its BERT formula
counts 2 per multiply-add), so the MFU it printed for ResNet-50 was half of
what this gives. XLA's own cost analysis of the bs-128 step read 3.07 TFLOP,
24.0 GFLOP per image (PERF.md section 6, entry 1), which agrees with this.
"""

STAGES = {50: ((3, 64), (4, 128), (6, 256), (3, 512))}


def forward_macs(depth, image_size, class_dim):
    r = image_size // 2
    macs = r * r * 7 * 7 * 3 * 64               # stem 7x7/2
    r //= 2                                      # 3x3/2 max pool
    inc = 64
    for stage, (blocks, mid) in enumerate(STAGES[depth]):
        for b in range(blocks):
            out = r // (2 if b == 0 and stage > 0 else 1)
            macs += r * r * inc * mid            # 1x1, before the stride
            macs += out * out * 9 * mid * mid    # 3x3, carries the stride
            macs += out * out * mid * 4 * mid    # 1x1 expansion
            if b == 0:
                macs += out * out * inc * 4 * mid    # projection shortcut
            inc, r = 4 * mid, out
    return macs + inc * class_dim


def per_sample(config, traffic):
    m = config['model']
    return 6.0 * forward_macs(m['depth'], traffic['image_size'],
                              m['class_dim'])
