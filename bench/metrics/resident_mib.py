"""Device bytes in use after the window, with the writer, the replica and
the snapshot still held, in MiB."""


def read(run):
    return run.resident_bytes / 2**20 if run.resident_bytes > 0 else None
