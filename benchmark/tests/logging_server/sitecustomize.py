"""An observer for the server, put on the child's PYTHONPATH by test_run.py: every
request the REST layer dispatches is written, as it arrived, to the file that
`BENCH_TEST_REQUESTS` names: one JSON object a line, `method`, `path`, `params`."""

import json
import os
import threading

import elasticsearch_tpu.rest.controller as controller

_dispatch = controller.RestController.dispatch
_lock = threading.Lock()


def _logged_dispatch(self, request):
    with _lock, open(os.environ["BENCH_TEST_REQUESTS"], "a") as f:
        f.write(json.dumps({"method": request.method, "path": request.path,
                            "params": dict(request.params)}) + "\n")
    return _dispatch(self, request)


controller.RestController.dispatch = _logged_dispatch
