"""Model FLOPs of one optimizer step, in closed form from the sizes.

A multiply and an add count as two operations, as the published peaks count
them.  Only the matrix work of the forward pass and its two backward
products counts (3x the forward); recomputed operations never count, nor do
element-wise work, norms, softmax or the optimizer.
"""


def resnet_forward(arch, image_size, num_classes, width=64):
    """Forward FLOPs of one image through a torchvision-style ResNet.

    ResNet-50 at 224 gives 8.18e9: the "4.09 GFLOPs" usually quoted (and
    ``bench.py:_resnet_flops_per_step``'s 4.089e9) are multiply-adds.
    """
    stages, bottleneck = {"resnet18": ([2, 2, 2, 2], False),
                          "resnet34": ([3, 4, 6, 3], False),
                          "resnet50": ([3, 4, 6, 3], True),
                          "resnet101": ([3, 4, 23, 3], True),
                          "resnet152": ([3, 8, 36, 3], True)}[arch]
    conv = lambda hw, k, cin, cout: 2 * hw * hw * k * k * cin * cout
    half = lambda n: (n + 1) // 2
    hw = half(image_size)                               # 7x7 stem, stride 2
    total = conv(hw, 7, 3, width)
    hw = half(hw)                                       # 3x3 max pool, stride 2
    cin = width
    for i, n_blocks in enumerate(stages):
        f = width * 2 ** i
        cout = 4 * f if bottleneck else f
        for j in range(n_blocks):
            out_hw = half(hw) if i > 0 and j == 0 else hw
            if bottleneck:
                total += (conv(hw, 1, cin, f) + conv(out_hw, 3, f, f)
                          + conv(out_hw, 1, f, cout))
            else:
                total += conv(out_hw, 3, cin, f) + conv(out_hw, 3, f, f)
            if cin != cout or out_hw != hw:
                total += conv(out_hw, 1, cin, cout)     # downsample
            cin, hw = cout, out_hw
    return total + 2 * cin * num_classes


def resnet_train(model, batch):
    return 3 * batch * resnet_forward(model["arch"], model["image_size"],
                                      model["num_classes"])


def gpt_forward(model, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens: the dense kernels of
    every block, causal attention (half of the ``seq x seq`` products) and
    the tied head.  Embedding look-ups are not matrix work."""
    d, inner, layers = model["n_embd"], model["n_inner"], model["n_layer"]
    dense = layers * (4 * d * d + 2 * d * inner)        # parameters in kernels
    attention = layers * 2 * seq * d // 2               # QK^T and PV, per token
    return 2 * seq * (dense + attention + d * model["vocab_size"])


def gpt_train(model, batch, seq):
    return 3 * batch * gpt_forward(model, seq)
