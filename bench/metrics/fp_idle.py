"""Share of the traced span in which the chip is idle while the host is
in fingerprint work (a ``serve.fp.publish`` or ``serve.fp.verify``
span): RRNS codewords encoded or checked, one host round trip each."""
import program_spans


def read(run):
    return program_spans.idle_share(run, "fp")
