"""Management REST API + Prometheus exposition.

A compact analogue of `emqx_management`'s minirest API
(/root/reference/apps/emqx_management/src, ~15.6 kLoC of OpenAPI
handlers) and `emqx_prometheus` (/root/reference/apps/emqx_prometheus/
src/emqx_prometheus.erl): read endpoints for clients/subscriptions/
routes/rules/stats/metrics, write endpoints for publish/kick/rules, and
a ``/metrics`` scrape in Prometheus text exposition format.  Served
with aiohttp on the broker's event loop.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

from aiohttp import web

from .message import Message


def _json(data, status: int = 200, headers=None) -> web.Response:
    return web.json_response(data, status=status, headers=headers)


async def _body_json(request: web.Request) -> dict:
    """Optional JSON body: absent or malformed -> {}."""
    try:
        return await request.json() if request.can_read_body else {}
    except json.JSONDecodeError:
        return {}


class MgmtApi:
    # routes reachable without credentials: the login endpoint, the
    # status page (which degrades to a login hint when anonymous, the
    # way the reference serves dashboard assets openly and gates the
    # data), and the Prometheus scrape (open by default in the
    # reference; gate it with api.prometheus_auth=true)
    _OPEN = {
        ("POST", "/api/v5/login"),
        ("GET", "/"),
        ("GET", "/dashboard"),
    }

    def __init__(self, server, bind: str = "127.0.0.1", port: int = 0) -> None:
        self.server = server  # BrokerServer
        self.broker = server.broker
        self.bind = bind
        self.port = port
        self._runner: Optional[web.AppRunner] = None
        from .mgmt_auth import AuditLog, MgmtAuth

        cfg = self.broker.config.api
        self.auth = MgmtAuth(
            cfg.data_dir,
            default_username=cfg.default_username,
            default_password=cfg.default_password,
            token_ttl=cfg.token_ttl,
        )
        self.prometheus_auth = cfg.prometheus_auth
        # audit trail of mutating API calls (emqx_audit's role),
        # persisted across restarts, surfaced at /api/v5/audit
        self.audit = AuditLog(cfg.data_dir)
        # schema registry persistence: REST-registered schemas reload
        # on restart (rules reference them by name)
        from .schema_registry import global_registry

        global_registry().load(
            os.path.join(cfg.data_dir, "schemas.json")
        )
        # failed-login throttle: remote -> recent failure monotonics
        self._login_failures: dict = {}

    @property
    def audit_log(self) -> list:
        return self.audit.entries

    @web.middleware
    async def _auth_middleware(self, request: web.Request, handler):
        """401 on every management route without credentials
        (emqx_mgmt_auth / emqx_dashboard authn+RBAC): Bearer admin
        token or Basic api-key; viewers are read-only."""
        path, method = request.path, request.method
        open_route = (
            (method, path) in self._OPEN
            or (path == "/metrics" and not self.prometheus_auth)
        )
        ident = self.auth.authenticate_header(
            request.headers.get("Authorization")
        )
        if not open_route:
            if ident is None:
                return _json(
                    {"code": "UNAUTHORIZED",
                     "message": "login or api key required"},
                    status=401,
                    headers={
                        # lets browsers/tools prompt for an api key
                        "WWW-Authenticate":
                        'Basic realm="emqx_tpu api key"',
                    },
                )
            if ident.publish_only:
                # the publisher role is an ingestion credential: the
                # publish endpoint and nothing else, reads included
                if method == "POST" and path in (
                    "/api/v5/publish", "/api/v5/publish/bulk"
                ):
                    request["identity"] = ident
                    return await self._audited(request, handler, ident)
                return _json(
                    {"code": "FORBIDDEN",
                     "message": "publisher role: publish only"},
                    status=403,
                )
            if path.startswith("/api/v5/data/") and not ident.can_write:
                # backup archives hold the full config (secrets
                # included): administrator-only, even for downloads
                return _json(
                    {"code": "FORBIDDEN",
                     "message": "administrator required"},
                    status=403,
                )
            self_pwd_change = (
                ident.via == "token"
                and method == "PUT"
                and path == f"/api/v5/users/{ident.actor}/change_pwd"
            )
            if (method not in ("GET", "HEAD") and not ident.can_write
                    and not self_pwd_change):
                # viewers are read-only — except rotating their OWN
                # password, which change_pwd re-verifies with old_pwd
                return _json(
                    {"code": "FORBIDDEN",
                     "message": "viewer role is read-only"},
                    status=403,
                )
        request["identity"] = ident
        return await self._audited(request, handler, ident)

    async def _audited(self, request, handler, ident):
        method, path = request.method, request.path
        resp = await handler(request)
        if method in ("POST", "PUT", "DELETE") and path != "/api/v5/login":
            self.audit.append(
                {
                    "at": time.time(),
                    "actor": ident.actor if ident else None,
                    "via": ident.via if ident else None,
                    "method": method,
                    "path": path,
                    "from": request.remote,
                    "status": resp.status,
                }
            )
        return resp

    # ------------------------------------------------------- lifecycle

    async def start(self) -> None:
        app = web.Application()  # default 1 MiB body cap: the open
        # login route must not buffer attacker-sized bodies; the
        # import handler streams its own (authenticated) larger limit
        r = app.router
        r.add_post("/api/v5/login", self.post_login)
        r.add_get("/api/v5/api_key", self.get_api_keys)
        r.add_post("/api/v5/api_key", self.post_api_key)
        r.add_delete("/api/v5/api_key/{key}", self.delete_api_key)
        r.add_get("/api/v5/users", self.get_users)
        r.add_post("/api/v5/users", self.post_user)
        r.add_delete("/api/v5/users/{username}", self.delete_user)
        r.add_put("/api/v5/users/{username}/change_pwd", self.change_pwd)
        r.add_get("/api/v5/clients", self.get_clients)
        r.add_get("/api/v5/clients/{clientid}", self.get_client)
        r.add_delete("/api/v5/clients/{clientid}", self.kick_client)
        r.add_get("/api/v5/subscriptions", self.get_subscriptions)
        r.add_get("/api/v5/topics", self.get_topics)
        r.add_get("/api/v5/mqtt/topic_metrics", self.get_topic_metrics)
        r.add_post("/api/v5/mqtt/topic_metrics",
                   self.post_topic_metrics)
        r.add_delete("/api/v5/mqtt/topic_metrics/{topic}",
                     self.delete_topic_metrics)
        r.add_get("/api/v5/stats", self.get_stats)
        r.add_get("/api/v5/metrics", self.get_metrics)
        r.add_get("/api/v5/nodes", self.get_nodes)
        r.add_get("/api/v5/rules", self.get_rules)
        r.add_post("/api/v5/rules", self.post_rule)
        r.add_delete("/api/v5/rules/{rule_id}", self.delete_rule)
        r.add_post("/api/v5/publish", self.post_publish)
        r.add_get("/api/v5/alarms", self.get_alarms)
        r.add_delete("/api/v5/alarms", self.clear_alarms)
        r.add_get("/api/v5/failpoints", self.get_failpoints)
        r.add_put("/api/v5/failpoints/{name}", self.put_failpoint)
        r.add_delete("/api/v5/failpoints/{name}", self.delete_failpoint)
        r.add_delete("/api/v5/failpoints", self.delete_failpoints)
        r.add_get("/api/v5/banned", self.get_banned)
        r.add_post("/api/v5/banned", self.post_banned)
        r.add_delete("/api/v5/banned/{kind}/{who}", self.delete_banned)
        r.add_get("/api/v5/slow_subscriptions", self.get_slow_subs)
        r.add_get("/api/v5/olp", self.get_olp)
        r.add_get("/api/v5/flight", self.get_flight)
        r.add_post("/api/v5/flight/dump", self.post_flight_dump)
        r.add_get("/api/v5/flight/{id}", self.get_flight_dump)
        r.add_get("/api/v5/profiler", self.get_profiler)
        r.add_get("/api/v5/profiler/trace", self.get_profiler_trace)
        r.add_delete("/api/v5/profiler", self.reset_profiler)
        r.add_get("/api/v5/tracing", self.get_tracing)
        r.add_put("/api/v5/tracing", self.put_tracing)
        r.add_delete("/api/v5/tracing", self.reset_tracing)
        r.add_get("/api/v5/tracing/traces", self.get_tracing_traces)
        r.add_get(
            "/api/v5/tracing/traces/{trace_id}", self.get_tracing_trace
        )
        r.add_get(
            "/api/v5/tracing/messages/{mid}", self.get_tracing_by_mid
        )
        r.add_get("/api/v5/tracing/spans", self.get_tracing_spans)
        r.add_get("/api/v5/tracing/trace", self.get_tracing_perfetto)
        r.add_get("/api/v5/trace", self.get_traces)
        r.add_post("/api/v5/trace", self.post_trace)
        r.add_delete("/api/v5/trace/{name}", self.delete_trace)
        r.add_get("/api/v5/trace/{name}/log", self.get_trace_log)
        r.add_get("/api/v5/audit", self.get_audit)
        r.add_put("/api/v5/configs", self.put_config)
        r.add_post("/api/v5/data/export", self.post_export)
        r.add_get("/api/v5/data/export/{name}", self.get_export_file)
        r.add_post("/api/v5/data/import", self.post_import)
        r.add_get("/api/v5/schema_registry", self.get_schemas)
        r.add_post("/api/v5/schema_registry", self.post_schema)
        r.add_delete("/api/v5/schema_registry/{name}", self.delete_schema)
        r.add_get("/api/v5/gcp_devices", self.get_gcp_devices)
        r.add_post("/api/v5/gcp_devices", self.post_gcp_devices)
        r.add_get(
            "/api/v5/gcp_devices/{deviceid:.+}", self.get_gcp_device
        )
        r.add_put(
            "/api/v5/gcp_devices/{deviceid:.+}", self.put_gcp_device
        )
        r.add_delete(
            "/api/v5/gcp_devices/{deviceid:.+}", self.delete_gcp_device
        )
        r.add_get("/api/v5/gateways", self.get_gateways)
        r.add_get("/api/v5/plugins", self.get_plugins)
        r.add_get("/", self.dashboard)
        r.add_get("/dashboard", self.dashboard)
        r.add_post(
            "/api/v5/load_rebalance/evacuation/start", self.start_evacuation
        )
        r.add_post(
            "/api/v5/load_rebalance/evacuation/stop", self.stop_evacuation
        )
        r.add_post(
            "/api/v5/load_rebalance/start", self.start_rebalance
        )
        r.add_post("/api/v5/load_rebalance/stop", self.stop_rebalance)
        r.add_post(
            "/api/v5/load_rebalance/purge/start", self.start_purge
        )
        r.add_post(
            "/api/v5/load_rebalance/purge/stop", self.stop_purge
        )
        r.add_get("/api/v5/load_rebalance/status", self.rebalance_status)
        r.add_get("/metrics", self.prometheus)
        app.middlewares.append(self._auth_middleware)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.bind, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # ------------------------------------------------------------ auth

    _LOGIN_WINDOW = 60.0
    _LOGIN_MAX_FAILURES = 10

    async def post_login(self, request: web.Request) -> web.Response:
        """Dashboard-style login: credentials -> Bearer token
        (emqx_dashboard_admin:sign_token). The only unauthenticated
        mutating route, so it is (a) throttled per remote after
        repeated failures and (b) runs its 50k-round PBKDF2 in a
        worker thread — on the event loop it would stall every
        connected MQTT client for tens of ms per attempt."""
        import asyncio as _aio

        try:
            body = await request.json()
            username = str(body["username"])
            password = str(body["password"])
        except (KeyError, TypeError, json.JSONDecodeError):
            return _json({"code": "BAD_REQUEST"}, status=400)
        now = time.monotonic()
        remote = request.remote or "?"
        failures = [
            t for t in self._login_failures.get(remote, ())
            if now - t < self._LOGIN_WINDOW
        ]
        if len(failures) >= self._LOGIN_MAX_FAILURES:
            self._login_failures[remote] = failures
            return _json(
                {"code": "TOO_MANY_REQUESTS",
                 "message": "too many failed logins; retry later"},
                status=429,
            )
        token = await _aio.get_running_loop().run_in_executor(
            None, self.auth.login, username, password
        )
        if token is None:
            failures.append(now)
            self._login_failures[remote] = failures
            if len(self._login_failures) > 10_000:
                self._login_failures.clear()  # bound the table
            return _json(
                {"code": "BAD_USERNAME_OR_PWD"}, status=401
            )
        self._login_failures.pop(remote, None)
        user = self.auth.admins[username]
        return _json({
            "token": token,
            "role": user["role"],
            "version": "5.8",
        })

    async def get_api_keys(self, request: web.Request) -> web.Response:
        return _json({"data": self.auth.info()})

    async def post_api_key(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            key, secret = self.auth.create_api_key(
                body["name"],
                role=body.get("role", "administrator"),
                expires_in=body.get("expires_in"),
                enabled=bool(body.get("enable", True)),
            )
        except (KeyError, ValueError, TypeError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        # the plaintext secret appears in this response and never again
        return _json({"api_key": key, "api_secret": secret}, status=201)

    async def delete_api_key(self, request: web.Request) -> web.Response:
        ok = self.auth.delete_api_key(request.match_info["key"])
        return web.Response(status=204 if ok else 404)

    async def get_users(self, request: web.Request) -> web.Response:
        return _json({"data": [
            {"username": u, "role": e["role"]}
            for u, e in self.auth.admins.items()
        ]})

    async def post_user(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            username = str(body["username"])
            if username in self.auth.admins:
                return _json({"code": "ALREADY_EXISTS"}, status=409)
            self.auth.add_admin(
                username,
                str(body["password"]),
                role=body.get("role", "viewer"),
            )
        except (KeyError, ValueError, TypeError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return _json({"username": username}, status=201)

    async def delete_user(self, request: web.Request) -> web.Response:
        username = request.match_info["username"]
        ident = request["identity"]
        if ident is not None and ident.via == "token" \
                and ident.actor == username:
            return _json(
                {"code": "BAD_REQUEST",
                 "message": "cannot delete the logged-in user"}, 400
            )
        try:
            ok = self.auth.delete_admin(username)
        except ValueError as exc:
            # the last administrator is undeletable: it would lock the
            # plane and re-seed default credentials on restart
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return web.Response(status=204 if ok else 404)

    async def change_pwd(self, request: web.Request) -> web.Response:
        username = request.match_info["username"]
        try:
            body = await request.json()
            ok = self.auth.change_password(
                username, str(body["old_pwd"]), str(body["new_pwd"])
            )
        except (KeyError, ValueError, TypeError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        if not ok:
            return _json({"code": "BAD_USERNAME_OR_PWD"}, status=401)
        return web.Response(status=204)

    # --------------------------------------------------------- clients

    async def get_clients(self, request: web.Request) -> web.Response:
        cm = self.broker.cm
        out = []
        for cid in cm.clients():
            session = cm.lookup(cid)
            if session is None:
                continue
            out.append(
                {
                    "clientid": cid,
                    "connected": cm.connected(cid),
                    **session.info(),
                }
            )
        return _json({"data": out, "meta": {"count": len(out)}})

    async def get_client(self, request: web.Request) -> web.Response:
        cid = request.match_info["clientid"]
        session = self.broker.cm.lookup(cid)
        if session is None:
            return _json({"code": "NOT_FOUND"}, status=404)
        return _json(
            {
                "clientid": cid,
                "connected": self.broker.cm.connected(cid),
                **session.info(),
            }
        )

    async def kick_client(self, request: web.Request) -> web.Response:
        cid = request.match_info["clientid"]
        if not self.broker.cm.kick(cid):
            return _json({"code": "NOT_FOUND"}, status=404)
        return web.Response(status=204)

    # --------------------------------------------------- subscriptions

    async def get_subscriptions(self, request: web.Request) -> web.Response:
        out = []
        router = self.broker.router
        for cid in self.broker.cm.clients():
            for flt in sorted(router.subscriptions_of(cid)):
                out.append({"clientid": cid, "topic": flt})
        return _json({"data": out, "meta": {"count": len(out)}})

    async def get_topics(self, request: web.Request) -> web.Response:
        topics = sorted(self.broker.router.topics())
        node = self.broker.config.node_name
        return _json(
            {
                "data": [{"topic": t, "node": node} for t in topics],
                "meta": {"count": len(topics)},
            }
        )

    async def get_topic_metrics(self, request: web.Request):
        return _json({"data": self.broker.topic_metrics.info()})

    async def post_topic_metrics(self, request: web.Request):
        body = await request.json()
        topic = str(body.get("topic", ""))
        try:
            created = self.broker.topic_metrics.register(topic)
        except ValueError as exc:
            return _json({"code": "BAD_REQUEST",
                          "message": str(exc)}, status=400)
        if not created:
            return _json({"code": "ALREADY_EXISTS",
                          "message": "topic already registered"},
                         status=409)
        return _json({"topic": topic}, status=201)

    async def delete_topic_metrics(self, request: web.Request):
        from urllib.parse import unquote

        topic = unquote(request.match_info["topic"])
        if not self.broker.topic_metrics.unregister(topic):
            return _json({"code": "NOT_FOUND",
                          "message": "topic not registered"},
                         status=404)
        return web.Response(status=204)

    # ------------------------------------------------------ stats/meta

    async def get_stats(self, request: web.Request) -> web.Response:
        stats = self.broker.stats.all()
        stats["connections.count"] = len(self.broker.cm)
        stats["retained.count"] = len(self.broker.retainer)
        return _json(stats)

    async def get_metrics(self, request: web.Request) -> web.Response:
        return _json(self.broker.metrics.all())

    async def get_nodes(self, request: web.Request) -> web.Response:
        # this node's row (resume depth, olp level, durability surface,
        # multicore attachment) + every alive peer's row over the
        # cluster node_info RPC: ANY worker's api port serves the whole
        # pool's merged view
        data = [self.broker.node_info()]
        ext = self.broker.external
        cluster = ext.info() if ext is not None else {}
        if ext is not None:
            fetch = getattr(ext, "fetch_node_infos", None)
            if fetch is not None:
                data += await fetch()
        return _json({"data": data, "cluster": cluster})

    # ----------------------------------------------------------- rules

    async def get_rules(self, request: web.Request) -> web.Response:
        # "stats" carries the columnar-eval surface: lowered-vs-
        # fallback registry split, matrix/scalar window counts, the
        # engine's per-cell cost EWMAs and breaker state; "egress" the
        # per-sink queue depth / batch-size percentiles / breaker view
        return _json({
            "data": self.broker.rules.info(),
            "stats": self.broker.rules.stats(),
            "egress": self.broker.resources.info(),
        })

    async def post_rule(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            rule = self.broker.rules.add_rule(
                body["id"],
                body["sql"],
                enabled=body.get("enable", True),
                description=body.get("description", ""),
            )
        except (KeyError, ValueError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return _json({"id": rule.rule_id, "sql": rule.sql}, status=201)

    async def delete_rule(self, request: web.Request) -> web.Response:
        if not self.broker.rules.remove_rule(request.match_info["rule_id"]):
            return _json({"code": "NOT_FOUND"}, status=404)
        return web.Response(status=204)

    # --------------------------------------------------------- publish

    async def post_publish(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            msg = Message(
                topic=body["topic"],
                payload=str(body.get("payload", "")).encode(),
                qos=int(body.get("qos", 0)),
                retain=bool(body.get("retain", False)),
                from_client=body.get("clientid", "http_api"),
            )
        except (KeyError, ValueError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        batcher = self.broker.batcher
        if batcher is not None:
            n = await batcher.publish(msg)
        else:
            n = self.broker.publish(msg)
        return _json({"delivered": n})

    # ------------------------------------------------- alarms / banned

    async def get_alarms(self, request: web.Request) -> web.Response:
        which = request.query.get("activated", "true") == "true"
        alarms = (
            self.broker.alarms.active()
            if which
            else self.broker.alarms.history()
        )
        return _json(
            {
                "data": [
                    {
                        "name": a.name,
                        "message": a.message,
                        "details": a.details,
                        "activated_at": a.activated_at,
                        "deactivated_at": a.deactivated_at,
                    }
                    for a in alarms
                ]
            }
        )

    async def clear_alarms(self, request: web.Request) -> web.Response:
        for a in self.broker.alarms.active():
            self.broker.alarms.deactivate(a.name)
        return web.Response(status=204)

    # ------------------------------------------------------ failpoints

    async def get_failpoints(self, request: web.Request) -> web.Response:
        from . import failpoints

        eng = self.broker.router.engine
        return _json({
            "enabled": failpoints.enabled,
            "data": failpoints.list_points(),
            "seams": list(failpoints.SEAMS),
            "engine_breaker": eng.breaker_info(),
        })

    async def put_failpoint(self, request: web.Request) -> web.Response:
        from . import failpoints

        body = await _body_json(request)
        action = body.get("action")
        if action not in failpoints.ACTIONS:
            return _json(
                {"error": f"action must be one of {failpoints.ACTIONS}"},
                status=400,
            )
        kw = {}
        try:
            for k in ("prob", "delay"):
                if body.get(k) is not None:
                    kw[k] = float(body[k])
            for k in ("after", "times", "seed"):
                if body.get(k) is not None:
                    kw[k] = int(body[k])
        except (TypeError, ValueError):
            return _json(
                {"error": "prob/delay must be numbers; "
                          "after/times/seed integers"},
                status=400,
            )
        if body.get("match") is not None:
            kw["match"] = str(body["match"])
        info = failpoints.configure(
            request.match_info["name"], action, **kw
        )
        return _json(info)

    async def delete_failpoint(self, request: web.Request) -> web.Response:
        from . import failpoints

        if not failpoints.clear(request.match_info["name"]):
            return _json({"error": "no such failpoint"}, status=404)
        return web.Response(status=204)

    async def delete_failpoints(self, request: web.Request) -> web.Response:
        from . import failpoints

        failpoints.clear()
        return web.Response(status=204)

    async def get_banned(self, request: web.Request) -> web.Response:
        return _json({"data": self.broker.banned.all()})

    async def post_banned(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            self.broker.banned.ban(
                body["as"],
                body["who"],
                seconds=body.get("seconds"),
                reason=body.get("reason", ""),
            )
        except (KeyError, ValueError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return _json({"as": body["as"], "who": body["who"]}, status=201)

    async def delete_banned(self, request: web.Request) -> web.Response:
        ok = self.broker.banned.unban(
            request.match_info["kind"], request.match_info["who"]
        )
        return web.Response(status=204 if ok else 404)

    async def get_olp(self, request: web.Request) -> web.Response:
        """Overload-protection ladder state: level, the last signal
        snapshot vs thresholds, shed/deferred/refused counters, and
        the recent transition ring."""
        return _json(self.broker.olp.info())

    async def get_slow_subs(self, request: web.Request) -> web.Response:
        return _json({"data": self.broker.slow_subs.top()})

    # -------------------------------------------------------- profiler

    async def get_profiler(self, request: web.Request) -> web.Response:
        """Window-pipeline profiler dump: stage-latency histogram
        summaries, the engine's gauge surface, and the flight
        recorder's most recent windows + engine lifecycle events
        (``?windows=N`` bounds the dump).  A window's record holds its
        laps and sub-stages (``stages_us``: ``collect`` inside
        ``batch_wait``; ``<name>_cpu``, the CPU seconds of an engine
        section on its executor thread: wall far over CPU in
        ``overlay``, which calls nothing that blocks, is GIL
        contention) and what the loop thread did
        since the record before: reads and writes
        (``loop_ingress_*``, ``loop_egress_*``), its CPU
        (``loop_cpu_us``), the phases of its turn, which add up to
        the wall time between the two (``loop_poll_us``: inside
        ``select``, a loop that never polls is saturated;
        ``loop_recv_us``: the turns' ``recv`` calls;
        ``loop_reads_us``: the reads handled in a row;
        ``loop_acks_us``: the publishers' acknowledgements;
        ``loop_tail_us``: the rest, the window's own laps among it;
        ``loop_turns``, ``loop_recv_turns``), and the process's
        collections (``gc_us``, ``gc_collections``)."""
        prof = self.broker.profiler
        try:
            limit = int(request.query.get("windows", 32))
        except ValueError:
            return _json({"code": "BAD_REQUEST",
                          "message": "windows must be an integer"}, 400)
        return _json({
            "enabled": prof.enabled,
            "histograms_us": prof.summary(),
            "engine": self.broker.router.engine.stats(),
            "slow_subs": self.broker.slow_subs.top(),
            "windows": prof.windows(limit),
            "events": prof.events(limit),
        })

    async def get_profiler_trace(self, request: web.Request) -> web.Response:
        """The flight recorder as Chrome trace-event JSON — loads
        directly in Perfetto (ui.perfetto.dev) or chrome://tracing, so
        a stall is diagnosable post-hoc without a reproducer.  The
        loop's track carries four kinds of burst: ``loop_poll_wait``
        (inside ``select``), ``loop_recv`` (a turn's ``recv`` calls),
        ``loop_ingress`` and ``loop_egress`` (reads handled, writes);
        a collection over ``flight.gc_stall_ms`` is a ``gc_pause`` on
        track 0."""
        prof = self.broker.profiler
        limit = None
        if "windows" in request.query:
            try:
                limit = int(request.query["windows"])
            except ValueError:
                return _json({"code": "BAD_REQUEST",
                              "message": "windows must be an integer"},
                             400)
        return _json(prof.chrome_trace(limit))

    async def reset_profiler(self, request: web.Request) -> web.Response:
        self.broker.profiler.reset()
        return web.Response(status=204)

    # -------------------------------------------------- flight recorder

    async def get_flight(self, request: web.Request) -> web.Response:
        """Flight-recorder status for this process plus every dump id
        retrievable from the shared dump directory — a multicore
        pool's workers and match service persist into ONE directory,
        so any worker's API port lists the whole pool's captures."""
        from . import flightrec
        fl = self.broker.flight
        return _json({
            "status": fl.status(),
            "dumps": flightrec.list_dump_ids(fl.dump_dir),
        })

    async def get_flight_dump(self, request: web.Request) -> web.Response:
        """One correlated capture: every process's dump for the
        trigger id merged into a single Perfetto-loadable Chrome trace
        with per-process tracks.  ``?raw=1`` returns the raw dump
        documents instead of the merged timeline."""
        from . import flightrec
        fl = self.broker.flight
        trig_id = request.match_info["id"]
        docs, torn = flightrec.collect_dumps(fl, trig_id)
        if not docs:
            return _json({"code": "NOT_FOUND",
                          "message": f"no flight dump {trig_id!r}"}, 404)
        out: Dict = {
            "id": trig_id,
            "torn": torn,
            "processes": [
                {"node": d.get("node"), "role": d.get("role"),
                 "pid": d.get("pid"), "reason": d.get("reason"),
                 "at": d.get("at")}
                for d in docs
            ],
        }
        if request.query.get("raw"):
            out["dumps"] = docs
        else:
            out["trace"] = flightrec.merge_dumps(docs)
        return _json(out)

    async def post_flight_dump(self, request: web.Request) -> web.Response:
        """Operator-initiated capture ("dump now"): triggers a dump in
        this process and — over the worker↔service control stream —
        every attached peer process, correlated under one id."""
        fl = self.broker.flight
        if not fl.armed:
            return _json({"code": "NOT_FOUND",
                          "message": "flight recorder disabled"}, 404)
        trig_id = fl.trigger("manual", force=True)
        return _json({"id": trig_id, "status": fl.status()})

    # ------------------------------------------- lifecycle tracing

    async def get_tracing(self, request: web.Request) -> web.Response:
        """Sampler configuration + store stats for the per-message
        lifecycle tracer (tracecontext.py)."""
        return _json(self.broker.lifecycle.info())

    async def put_tracing(self, request: web.Request) -> web.Response:
        """Runtime sampler update: enable, sample_rate, topic_filters,
        seed — debug a live flow without a restart."""
        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
            rate = body.get("sample_rate")
            if rate is not None:
                rate = float(rate)
                if not 0.0 <= rate <= 1.0:
                    raise ValueError("sample_rate must be in [0, 1]")
            filters = body.get("topic_filters")
            if filters is not None:
                filters = [str(f) for f in filters]
            self.broker.lifecycle.configure(
                enable=body.get("enable"),
                sample_rate=rate,
                topic_filters=filters,
                seed=body.get("seed"),
            )
        except (TypeError, ValueError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return _json(self.broker.lifecycle.info())

    async def reset_tracing(self, request: web.Request) -> web.Response:
        self.broker.lifecycle.store.clear()
        return web.Response(status=204)

    async def get_tracing_traces(self, request: web.Request) -> web.Response:
        try:
            limit = int(request.query.get("limit", 64))
        except ValueError:
            return _json({"code": "BAD_REQUEST",
                          "message": "limit must be an integer"}, 400)
        return _json({"data": self.broker.lifecycle.store.traces(limit)})

    async def get_tracing_trace(self, request: web.Request) -> web.Response:
        tid = request.match_info["trace_id"]
        spans = self.broker.lifecycle.store.get(tid)
        if not spans:
            return _json({"code": "NOT_FOUND",
                          "message": f"no trace {tid}"}, 404)
        return _json({"trace_id": tid, "spans": spans})

    async def get_tracing_by_mid(self, request: web.Request) -> web.Response:
        """Message-id lookup: the hex mid every span carries (and the
        slow-subs board reports) opens directly as its full trace."""
        mid = request.match_info["mid"]
        store = self.broker.lifecycle.store
        tid = store.by_mid(mid)
        if tid is None:
            return _json({"code": "NOT_FOUND",
                          "message": f"no trace for message {mid}"}, 404)
        return _json({"trace_id": tid, "mid": mid,
                      "spans": store.get(tid)})

    async def get_tracing_spans(self, request: web.Request) -> web.Response:
        """Raw span dump (this node only) — the merge feed for a
        multi-node Perfetto timeline (``ctl tracing perfetto``
        concatenates several nodes' dumps)."""
        return _json({
            "node": self.broker.lifecycle.node,
            "data": self.broker.lifecycle.store.spans(),
        })

    async def get_tracing_perfetto(self, request: web.Request) -> web.Response:
        """The trace store as a Perfetto-loadable timeline: one
        process track per node/worker seen in the spans, flow events
        linking each forward hop (``?trace_id=`` narrows to one
        trace)."""
        from .tracecontext import chrome_trace

        store = self.broker.lifecycle.store
        tid = request.query.get("trace_id")
        spans = store.get(tid) if tid else store.spans()
        return _json(chrome_trace(spans))

    # ----------------------------------------------------- trace/audit

    async def get_traces(self, request: web.Request) -> web.Response:
        return _json({"data": self.broker.trace.list()})

    async def post_trace(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
            rule = self.broker.trace.start(
                body["name"],
                body["type"],
                body["match"],
                duration=body.get("duration"),
            )
        except (KeyError, ValueError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return _json({"name": rule.name, "file": rule.path}, status=201)

    async def delete_trace(self, request: web.Request) -> web.Response:
        ok = self.broker.trace.stop(request.match_info["name"])
        return web.Response(status=204 if ok else 404)

    async def get_trace_log(self, request: web.Request) -> web.Response:
        import os
        import re

        name = request.match_info["name"]
        if not re.fullmatch(r"[A-Za-z0-9_-]{1,64}", name):
            # same charset trace.start enforces: the name joins a path
            return _json({"code": "BAD_REQUEST"}, status=400)
        path = os.path.join(self.broker.trace.directory, f"{name}.log")
        if not os.path.exists(path):
            return _json({"code": "NOT_FOUND"}, status=404)
        with open(path) as f:
            return web.Response(text=f.read(), content_type="text/plain")

    async def get_audit(self, request: web.Request) -> web.Response:
        return _json({"data": list(self.audit_log)})

    async def put_config(self, request: web.Request) -> web.Response:
        """Runtime config update; with a cluster attached, the change
        journals through the conf-txn multicall so every node applies
        it (emqx_conf's cluster-wide update path)."""
        try:
            body = await request.json()
            path, value = body["path"], body["value"]
            ext = self.broker.external
            if ext is not None and hasattr(ext, "update_config"):
                # validate locally BEFORE journaling: a bad path must
                # return 400, not poison every node's journal
                self.broker.apply_config(path, value)
                if hasattr(ext, "update_config_async"):
                    # raft mode: the API call resolves (or fails) with
                    # the quorum commit, never silently
                    txn = await ext.update_config_async(path, value)
                else:
                    txn = ext.update_config(path, value)
                return _json({"path": path, "txn": list(txn)})
            self.broker.apply_config(path, value)
        except (KeyError, ValueError, TypeError, json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)}, 400)
        return _json({"path": path})

    async def post_export(self, request: web.Request) -> web.Response:
        """Write a backup archive (emqx_mgmt_data_backup export):
        state gathering runs ON the loop (it reads loop-owned
        structures — off-loop it would race concurrent publishes);
        only the tar/gzip/disk bytes work leaves the loop."""
        import asyncio

        from .backup import gather_state, write_archive

        members, manifest = gather_state(self.server)
        directory = os.path.join(
            self.broker.config.api.data_dir, "backups"
        )
        path = await asyncio.get_running_loop().run_in_executor(
            None, write_archive, members, directory
        )
        return _json({
            "filename": os.path.basename(path),
            **manifest,
        }, status=201)

    async def get_export_file(self, request: web.Request) -> web.Response:
        import re

        name = request.match_info["name"]
        if not re.fullmatch(r"emqx-export-[0-9-]+\.tar\.gz", name):
            return _json({"code": "BAD_REQUEST"}, status=400)
        path = os.path.join(
            self.broker.config.api.data_dir, "backups", name
        )
        if not os.path.exists(path):
            return _json({"code": "NOT_FOUND"}, status=404)
        # FileResponse streams off-loop (sendfile) instead of holding
        # the whole archive in memory on the event loop
        return web.FileResponse(path, headers={
            "Content-Type": "application/gzip",
            "Content-Disposition": f'attachment; filename="{name}"',
        })

    async def post_import(self, request: web.Request) -> web.Response:
        """Restore an uploaded archive (raw body) into this broker:
        untar/ungzip off-loop, then apply mutations ON the loop in
        chunks so client keepalives keep flowing during the restore."""
        import asyncio

        from .backup import apply_state_async, parse_archive

        # stream the body manually: the app-wide 1 MiB cap protects
        # the unauthenticated routes, while this (admin-only) upload
        # allows realistic archive sizes under its own bound
        max_size = 512 * 1024 * 1024
        chunks = []
        got = 0
        async for chunk in request.content.iter_chunked(1 << 20):
            got += len(chunk)
            if got > max_size:
                return _json(
                    {"code": "BAD_REQUEST",
                     "message": "archive exceeds 512 MiB"},
                    status=413,
                )
            chunks.append(chunk)
        data = b"".join(chunks)
        try:
            members = await asyncio.get_running_loop().run_in_executor(
                None, parse_archive, data
            )
        except ValueError as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)},
                         status=400)
        report = await apply_state_async(self.server, members)
        return _json(report)

    async def get_schemas(self, request: web.Request) -> web.Response:
        from .schema_registry import global_registry

        return _json({"data": global_registry().info()})

    async def post_schema(self, request: web.Request) -> web.Response:
        import asyncio

        from .schema_registry import global_registry

        try:
            body = await request.json()
            # protobuf registration shells out to protoc: keep that
            # (and the temp-file IO) off the event loop
            await asyncio.get_running_loop().run_in_executor(
                None, global_registry().add,
                body["name"], body["type"], body["source"],
            )
        except (KeyError, ValueError, TypeError, OSError,
                json.JSONDecodeError) as exc:
            return _json({"code": "BAD_REQUEST", "message": str(exc)},
                         status=400)
        return _json({"name": body["name"], "type": body["type"]},
                     status=201)

    async def delete_schema(self, request: web.Request) -> web.Response:
        from .schema_registry import global_registry

        ok = global_registry().remove(request.match_info["name"])
        return web.Response(status=204 if ok else 404)

    async def get_gateways(self, request: web.Request) -> web.Response:
        return _json({"data": self.broker.gateways.info()})

    async def get_plugins(self, request: web.Request) -> web.Response:
        return _json({"data": self.broker.plugins.info()})

    # -------------------------------------------------- gcp devices

    def _gcp_registry(self):
        reg = self.broker.gcp_devices
        if reg is None:
            raise web.HTTPNotImplemented(
                text=json.dumps({
                    "code": "NOT_ENABLED",
                    "message": "set gcp_device_enable: true",
                }),
                content_type="application/json",
            )
        return reg

    async def get_gcp_devices(self, request: web.Request) -> web.Response:
        devices = self._gcp_registry().list_devices()
        return _json({"data": devices, "meta": {"count": len(devices)}})

    async def post_gcp_devices(self, request: web.Request) -> web.Response:
        """Bulk import (emqx_gcp_device:import_devices): a JSON list
        of device objects."""
        reg = self._gcp_registry()
        try:
            body = await request.json()
            if not isinstance(body, list):
                raise ValueError("expected a JSON list of devices")
        except (ValueError, json.JSONDecodeError) as e:
            return _json({"code": "BAD_REQUEST", "message": str(e)},
                         status=400)
        imported, errors = reg.import_devices(body)
        return _json({"imported": imported, "errors": errors})

    async def get_gcp_device(self, request: web.Request) -> web.Response:
        device = self._gcp_registry().get_device(
            request.match_info["deviceid"]
        )
        if device is None:
            return _json({"code": "NOT_FOUND"}, status=404)
        return _json(device)

    async def put_gcp_device(self, request: web.Request) -> web.Response:
        reg = self._gcp_registry()
        try:
            body = await request.json()
            body["deviceid"] = request.match_info["deviceid"]
            reg.put_device(body)
        except (KeyError, TypeError, ValueError,
                json.JSONDecodeError) as e:
            return _json({"code": "BAD_REQUEST", "message": str(e)},
                         status=400)
        return _json(reg.get_device(request.match_info["deviceid"]))

    async def delete_gcp_device(
        self, request: web.Request
    ) -> web.Response:
        if not self._gcp_registry().remove_device(
            request.match_info["deviceid"]
        ):
            return _json({"code": "NOT_FOUND"}, status=404)
        return web.Response(status=204)

    async def dashboard(self, request: web.Request) -> web.Response:
        """The web dashboard: a single self-contained HTML app (see
        dashboard.py) that logs in against /api/v5/login and drives
        the same JSON API operators script against.  Served openly —
        like the reference serving SPA assets — while every data
        route stays behind auth."""
        from .dashboard import DASHBOARD_HTML

        return web.Response(
            text=DASHBOARD_HTML, content_type="text/html"
        )

    async def start_evacuation(self, request: web.Request) -> web.Response:
        body = await _body_json(request)
        try:
            await self.broker.eviction.start_evacuation(
                int(body.get("conn_evict_rate", 50))
            )
        except (TypeError, ValueError) as e:
            return _json({"code": "BAD_REQUEST", "message": str(e)},
                         status=400)
        except RuntimeError as e:
            return _json({"code": "CONFLICT", "message": str(e)},
                         status=409)
        return _json(self.broker.eviction.info())

    async def stop_evacuation(self, request: web.Request) -> web.Response:
        await self.broker.eviction.stop_evacuation()
        return _json(self.broker.eviction.info())

    async def start_rebalance(self, request: web.Request) -> web.Response:
        """Cluster-wide balance (POST /load_rebalance/start): plan
        donors from live connection counts and shed their excess."""
        body = await _body_json(request)
        try:
            await self.broker.rebalance.start(
                conn_evict_rate=int(body.get("conn_evict_rate", 50)),
                rel_conn_threshold=float(
                    body.get("rel_conn_threshold", 1.10)
                ),
            )
        except (TypeError, ValueError) as e:
            return _json({"code": "BAD_REQUEST", "message": str(e)},
                         status=400)
        return _json(self.broker.rebalance.info())

    async def stop_rebalance(self, request: web.Request) -> web.Response:
        await self.broker.rebalance.stop()
        return _json(self.broker.rebalance.info())

    async def start_purge(self, request: web.Request) -> web.Response:
        """Purge detached sessions (POST /load_rebalance/purge/start);
        body {"purge_rate": N, "cluster": true} fans out to peers."""
        body = await _body_json(request)
        try:
            rate = int(body.get("purge_rate", 500))
            await self.broker.purger.start_purge(rate)
        except (TypeError, ValueError) as e:
            return _json({"code": "BAD_REQUEST", "message": str(e)},
                         status=400)
        except RuntimeError as e:
            return _json({"code": "CONFLICT", "message": str(e)},
                         status=409)
        ext = self.broker.external
        if body.get("cluster") and ext is not None:
            for peer in ext.peers_alive():
                await ext.transport.cast(
                    peer, {"type": "session_purge", "rate": rate}
                )
        return _json(self.broker.purger.info())

    async def stop_purge(self, request: web.Request) -> web.Response:
        """Body {"cluster": true} also stops peers' purges."""
        body = await _body_json(request)
        await self.broker.purger.stop_purge()
        ext = self.broker.external
        if body.get("cluster") and ext is not None:
            for peer in ext.peers_alive():
                await ext.transport.cast(
                    peer, {"type": "session_purge", "stop": True}
                )
        return _json(self.broker.purger.info())

    async def rebalance_status(self, request: web.Request) -> web.Response:
        return _json({
            "evacuation": self.broker.eviction.info(),
            "rebalance": self.broker.rebalance.info(),
            "purge": self.broker.purger.info(),
        })

    # ------------------------------------------------------ prometheus

    async def prometheus(self, request: web.Request) -> web.Response:
        """Prometheus text exposition (emqx_prometheus.erl's collect
        families): counters + gauges with sanitized names and one
        HELP/TYPE per family, engine index/breaker/EWMA gauges, and
        the window profiler's stage-latency histograms as proper
        ``_bucket``/``_sum``/``_count`` families."""
        from .observability import prom_histogram_lines, prom_name

        lines: list = []
        seen: set = set()

        def emit(name: str, kind: str, value, help_text: str = "",
                 labels=None) -> None:
            metric = prom_name("emqx_" + name.replace(".", "_"))
            if metric not in seen:
                # one HELP/TYPE per FAMILY — a repeated TYPE line (or a
                # name colliding after sanitization) breaks strict
                # text-format parsers
                seen.add(metric)
                lines.append(f"# HELP {metric} {help_text or name}")
                lines.append(f"# TYPE {metric} {kind}")
            if labels:
                lab = ",".join(
                    f'{k}="{v}"' for k, v in sorted(labels.items())
                )
                lines.append(f"{metric}{{{lab}}} {value}")
            else:
                lines.append(f"{metric} {value}")

        for name, value in sorted(self.broker.metrics.all().items()):
            emit(name, "counter", value)
        stats = self.broker.stats.all()
        stats["connections.count"] = len(self.broker.cm)
        stats["retained.count"] = len(self.broker.retainer)
        for name, value in sorted(stats.items()):
            emit(name, "gauge", value)
        emit(
            "uptime_seconds",
            "gauge",
            int(time.time() - self.broker.metrics.start_time),
        )
        # engine observability gauges (index tier sizes, auto-policy
        # window counts, cost EWMAs, breaker state) — previously only
        # reachable from bench harness code
        for name, value in sorted(
            self.broker.router.engine.stats().items()
        ):
            if value is None:
                continue
            if isinstance(value, bool):
                value = int(value)
            if not isinstance(value, (int, float)):
                continue
            emit("engine_" + name, "gauge", value,
                 help_text=f"match engine {name}")
        # durable-store durability gauges (group-commit gate
        # watermarks, parked ack-windows, quarantine counts)
        if self.broker.durable is not None:
            ds_stats = self.broker.durable.sync_stats()
            for name, value in sorted(ds_stats.items()):
                if not isinstance(value, (int, float)) or isinstance(
                    value, bool
                ):
                    continue
                emit("ds_" + name, "gauge", value,
                     help_text=f"durable store {name}")
            # sharded store: per-shard breakdown as labeled gauges
            # (each shard's own unsynced watermark / parked windows /
            # quarantine counts)
            for row in ds_stats.get("per_shard") or ():
                shard = row.get("shard")
                for name, value in sorted(row.items()):
                    if name == "shard" or not isinstance(
                        value, (int, float)
                    ) or isinstance(value, bool):
                        continue
                    emit(
                        "ds_shard_" + name, "gauge", value,
                        labels={"shard": str(shard)},
                        help_text=f"durable store shard {name}",
                    )
        # rule-engine columnar-eval gauges (lowered/fallback registry
        # split, matrix vs scalar window counts, per-cell cost EWMAs)
        for name, value in sorted(self.broker.rules.stats().items()):
            if value is None:
                continue
            if isinstance(value, bool):
                value = int(value)
            if not isinstance(value, (int, float)):
                continue
            emit("rules_" + name, "gauge", value,
                 help_text=f"rule engine {name}")
        # sink-egress surface (PR 20 windowed pipeline): per-sink
        # labeled gauges plus ONE merged batch-size histogram family
        # (prom_histogram_lines has no label support; snapshots merge
        # losslessly per bucket)
        batch_snap = None
        for rid, row in sorted(self.broker.resources.info().items()):
            for name, value in sorted(row.items()):
                if isinstance(value, bool):
                    value = int(value)
                if not isinstance(value, (int, float)):
                    continue
                emit("sink_" + name, "gauge", value,
                     labels={"sink": rid},
                     help_text=f"sink egress {name}")
            w = self.broker.resources.get(rid)
            if w is not None:
                snap = w.batch_hist.snapshot()
                batch_snap = (
                    snap if batch_snap is None
                    else batch_snap.merge(snap)
                )
        if batch_snap is not None and batch_snap.count:
            family = prom_name("emqx_sink_batch_size")
            if family not in seen:
                seen.add(family)
                lines.extend(prom_histogram_lines(
                    family, batch_snap,
                    help_text="records per flushed sink batch "
                              "(all sinks merged)",
                ))
        prof = self.broker.profiler
        for name, snap in sorted(prof.snapshots().items()):
            family = prom_name(f"emqx_profiler_{name}_us")
            if family in seen:
                continue
            seen.add(family)
            lines.extend(prom_histogram_lines(
                family, snap,
                help_text=f"window pipeline stage '{name}' latency "
                          "in microseconds",
            ))
        # multicore surface: this worker's shm window ring (occupancy,
        # high-watermark, refusal counters) and the shared match
        # service's counters + per-stage histograms, as cached from
        # the control stream's last pong — any worker's scrape carries
        # the service's view
        svc_info = getattr(self.broker.router.engine, "service_info",
                           None)
        info = svc_info() if svc_info is not None else {}
        for name, value in sorted((info.get("ring") or {}).items()):
            if not isinstance(value, (int, float)) or isinstance(
                value, bool
            ):
                continue
            emit("multicore_ring_" + name, "gauge", value,
                 help_text=f"shm window ring {name}")
        remote = info.get("service") or {}
        for name, value in sorted((remote.get("stats") or {}).items()):
            emit("matchsvc_" + name, "counter", value,
                 help_text=f"match service {name}")
        if remote.get("routes") is not None:
            emit("matchsvc_routes", "gauge", remote["routes"],
                 help_text="match service route count")
        from .observability import HistogramSnapshot
        for name, raw in sorted((remote.get("hist") or {}).items()):
            family = prom_name(f"emqx_matchsvc_{name}_us")
            if family in seen or not isinstance(raw, dict):
                continue
            seen.add(family)
            lines.extend(prom_histogram_lines(
                family, HistogramSnapshot.from_dict(raw),
                help_text=f"match service stage '{name}' latency "
                          "in microseconds",
            ))
        return web.Response(
            text="\n".join(lines) + "\n",
            content_type="text/plain",
            charset="utf-8",
        )
