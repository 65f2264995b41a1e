"""One count the job kept over another, in percent."""


def read(observed, numerator, denominator):
    if not observed.get(denominator):
        return None
    return 100.0 * observed[numerator] / observed[denominator]
