"""Device-to-host reads per profile stream decoded.

The program's own counter, ``repro.core.stream_stats()``: its reads over
its streams, over the whole run (the warm-up's streams read as many).  The
serve loop decodes one stream per profiled step; a verified inline stream
reads its words once and each guard's recomputed checksum once more.
"""


def read(ctx, records):
    try:
        from repro.core import stream_stats
    except ImportError:          # a program without the counter
        return None
    stats = stream_stats()
    return stats["reads"] / stats["streams"] if stats["streams"] else None
