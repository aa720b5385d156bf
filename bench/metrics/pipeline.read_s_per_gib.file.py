"""Host seconds of the stream pipeline's read stage per GiB it read:
``JobStats.stage_s["read"]`` (block read, CRC check and decode, summed
over the reader threads) over the bytes of the attempts."""


def read(ctx):
    stage_s, nbytes = ctx.layer.get("stage_s"), ctx.layer.get("bytes_read")
    if not stage_s or not nbytes or "read" not in stage_s:
        return None
    return stage_s["read"] / (nbytes / 2**30)
