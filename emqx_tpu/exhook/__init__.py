"""exhook: gRPC HookProvider server — the graft surface for a stock
EMQX (reference contract: /root/reference/apps/emqx_exhook/priv/protos/
exhook.proto; bridge semantics: emqx_exhook_handler.erl:230-236).

`exhook_pb2` is generated from proto/exhook.proto and committed
(README, "Running", has the command for whoever edits the .proto); the
service layer is hand-wired generic handlers in server.py.
"""

from . import exhook_pb2 as pb
from .server import ExhookServer  # noqa: F401
