"""Host milliseconds per RRNS codeword published or verified: the summed
duration of the traced ``serve.fp.*`` spans over their summed
``codewords`` stats."""
import program_spans


def read(run):
    return program_spans.ms_per_codeword(program_spans.run_spans(run))
