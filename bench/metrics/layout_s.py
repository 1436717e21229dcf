"""Host seconds of the program's layout coercion (``registry.as_padded`` ->
``formats.host_to_padded``, to device, ``block_until_ready``); in a service
cell, of ``FitService(X_host, y)``, which coerces."""


def read(run):
    return run.host.get("layout_s")
