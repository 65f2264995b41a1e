"""The most bytes the fullest chip held while the cell ran (the harness's
memory sampler: buffers and what the runtime reserves for running
programs' temporaries, together at one poll)."""


def read(observed):
    peak = observed["memory"].peak()
    return peak / 1e9 if peak else None
