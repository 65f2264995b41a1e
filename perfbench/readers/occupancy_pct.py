"""Mean share of the server's slots that held a request, over the decode
rounds of the window."""


def read(observed):
    rounds = observed.get("occupancy")
    if not rounds:
        return None
    return 100.0 * sum(rounds) / len(rounds) / observed["slots"]
