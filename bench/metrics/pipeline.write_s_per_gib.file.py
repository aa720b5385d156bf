"""Host seconds of the stream pipeline's write stage per GiB it wrote:
``JobStats.stage_s["write"]`` (encode and fsynced atomic block writes,
summed over the writer threads) over the bytes of the attempts."""


def read(ctx):
    stage_s, nbytes = ctx.layer.get("stage_s"), ctx.layer.get("bytes_written")
    if not stage_s or not nbytes or "write" not in stage_s:
        return None
    return stage_s["write"] / (nbytes / 2**30)
