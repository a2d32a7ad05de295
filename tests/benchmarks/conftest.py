"""One of the harness's own tests is known to fail since PR 33, and
says so in the report.

``test_bench_scopes.py::test_a_layers_scope_comes_from_the_program``
was written (PR 26) against a program that lists no ``LAYER_SCOPES``:
its first assertion is that ``horovod_tpu/utils/scopes.py`` has none,
and it then uses ``moe_experts`` as a name of its own. ISSUE 33 asks
the program for ``LAYER_SCOPES = ("moe_dispatch", "moe_experts")`` and
forbids edits to the harness's files, so that assertion is now false.
The test runs against the program as it is and is reported ``xfailed``
with this reason; ``strict`` makes it fail loudly once it passes, which
is when the ``benchmark`` PR that drops the assertion deletes this file.
"""

import pytest

KNOWN_FALSE = {
    "test_a_layers_scope_comes_from_the_program":
        "asserts the program lists no LAYER_SCOPES; since PR 33 it lists "
        "the routed MLP's two (a benchmark PR drops the assertion)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        why = KNOWN_FALSE.get(getattr(item, "originalname", item.name))
        if why and item.path.name == "test_bench_scopes.py":
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))
