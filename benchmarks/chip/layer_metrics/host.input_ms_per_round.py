"""Host milliseconds per round spent building the round's token batches
(``federated_token_batches``) and handing them to the step, from the
benchmark's own span around that call."""


def read(rec):
    s = rec["spans"].get("bench.input")
    if not s:
        return None
    return 1e3 * sum(s) / len(s)
