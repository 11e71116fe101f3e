"""``hbm_compiled_gib``: arguments plus temporaries of the compiled step per
chip, from ``compiled.memory_analysis()`` (layer: device).  The runtime's
``peak_bytes_in_use`` leaves temporaries out (PERF.md)."""


def read(events, host, context):
    info = host["compile_info"]
    return (info["argument_bytes"] + info["temp_bytes"]) / 2 ** 30
