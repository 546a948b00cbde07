"""Share of the traced span in which the chip is idle while the host is
in the paged write barrier (``serve.write_barrier``: page allocation,
eviction, copy-on-write), its fingerprint verifies excluded."""
import program_spans


def read(run):
    return program_spans.idle_share(run, "barrier")
