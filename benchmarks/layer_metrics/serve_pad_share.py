"""Serve: percent of the tokens the window's device calls computed that were
padding — (rows x length of every call, less the documents' real tokens)
over rows x length, from the replica's own count of each batch it fired."""


def read(run):
    window = run["window"]
    if not window["padded_tokens_fired"]:
        return None
    return 100.0 * (1.0 - window["real_tokens_fired"]
                    / window["padded_tokens_fired"])
