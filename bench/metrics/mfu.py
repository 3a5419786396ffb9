"""Model step: the whole network's operations per frame (for the chain,
blocks and projection) times frames completed ``ok``, over the window
times the chips times the chip's bf16 peak, in percent."""


def read(ctx):
    if ctx.record.window_s <= 0:
        return None
    done = len(ctx.record.ok()) * ctx.network.frame_flops(ctx.config)
    return 100.0 * done / (ctx.record.window_s * ctx.chips
                           * ctx.peaks["bf16_flops"])
