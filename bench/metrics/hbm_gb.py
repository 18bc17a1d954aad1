"""Device memory the cell needs: the largest footprint (arguments +
outputs + temporaries - aliased, from the compiler's memory_analysis) over
the programs the window runs, in GB."""


def read(rec):
    if not rec.program_bytes:
        return None
    return max(rec.program_bytes.values()) / 1e9
