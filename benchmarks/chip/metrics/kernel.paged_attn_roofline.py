"""kernel.paged_attn_roofline (%): least time of the paged decode
attention over its measured device time.

The least time of one call (one layer of one step) is the larger of its
FLOPs over the bf16 peak and its bytes over HBM bandwidth, counting what
attention needs as the member's family counts it
(``paged_attention_cost``: for a dense GQA member, q and out of every
head and the live pages of every KV head), once for each of its
``attention_layers``.  Averaged over the calls the host launched in the
traced window, times the kernel's events on the device in it, over their
summed device time."""

from harness import flops as F
from harness import trace as T

# the Pallas kernel's own name; failing that, a Mosaic custom call (the
# paged kernel is the only one in these cells' decode and prefill steps)
KERNEL = ("paged_kernel", "custom-call", "custom_call", "mosaic")


def read(ctx):
    if ctx.trace is None or not ctx.decode_calls:
        return None
    name = T.first_present(ctx.trace, KERNEL, modules=False)
    if name is None:
        return None
    n_dev = T.count(ctx.trace, name, modules=False)
    t_dev = T.per_device_time(ctx.trace, name, modules=False)
    if n_dev <= 0 or t_dev <= 0:
        return None
    least = calls = 0
    for arch, lens in ctx.decode_calls:
        m, fam = ctx.models_by_arch[arch], ctx.family_of(arch)
        fl, by = fam.paged_attention_cost(m, lens, ctx.block_tokens)
        layers = fam.attention_layers(m)
        least += layers * F.roofline_seconds(fl, by, ctx.peak)
        calls += layers
    return 100.0 * (least / calls) * n_dev / t_dev
