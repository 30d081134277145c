"""Mean width of the batches the service executed in the window: specs
released from coalesced batches over the number of those batches
(``CertificationService.stats()`` counts the batches)."""


def read(run):
    return run.counters.get("batch_width")
