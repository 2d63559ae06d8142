"""derive_tokens_per_s (tokens/s, host clock): engine tokens generated for
the derives the clients got back, each derive weighted by the share of its
time inside the window, over the window's seconds."""


def read(run):
    if not run.answered("derive"):
        return None
    return run.prorated("derive", "tokens") / run.seconds
