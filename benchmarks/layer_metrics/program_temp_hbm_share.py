"""Device: the temporaries the window's program allocates for each of its
runs, per chip (`memory_analysis().temp_size_in_bytes` of the executable,
which the allocator's statistics do not count), over the chip's memory, in
per cent. In a training step they are the saved activations; in the
unified serving program, copies of the KV pool in another layout."""


def read(run, label=None):
    temp = run.facts.get("program_temp_bytes")
    if not temp or not run.peaks:
        return None
    return 100.0 * temp / run.peaks["hbm_bytes"]
