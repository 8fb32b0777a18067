"""helix-tpu CLI.

The counterpart of the reference's cobra CLI (``api/cmd/helix/root.go:45-72``
— serve/apply/chat/...), argparse-based:

- ``serve``      — control plane (router, profiles, heartbeats, sessions,
                   OpenAI passthrough).  Reference: ``helix serve``.
- ``serve-node`` — TPU node agent: applies a serving profile as in-process
  Engines and exposes the OpenAI surface.  Replaces the reference's sandbox
  node stack (compose-manager + inference-proxy + heartbeat).
- ``profile``    — validate / describe profile YAML (composeparse analogue).
- ``chat``       — one-shot chat against a server (reference: ``helix chat``).
"""

from __future__ import annotations

import argparse
import json
import sys


def _cmd_serve_node(args) -> int:
    from aiohttp import web

    from helix_tpu.control.node_agent import NodeAgent
    from helix_tpu.device.compile_cache import configure_compile_cache
    from helix_tpu.control.profile import ServingProfile
    from helix_tpu.serving.openai_api import OpenAIServer

    import logging

    from helix_tpu.serving.logbuf import install as install_logbuf

    # the node's log: INFO to stderr and to the /logs ring, from before the
    # profile is applied (the build says which device and which attention
    # backend it resolved, and how long load and warm-up took)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    install_logbuf()
    configure_compile_cache()   # before the first compile
    tunnel_mode = getattr(args, "tunnel", False)
    if tunnel_mode and not args.control_plane:
        print(
            "serve-node: --tunnel requires --control-plane (the tunnel "
            "dials out to it)", file=sys.stderr,
        )
        return 2
    agent = NodeAgent(
        runner_id=args.runner_id,
        heartbeat_url=args.control_plane,
        heartbeat_interval=args.heartbeat_interval,
        # tunnel mode advertises NO address: the control plane dispatches
        # through the reverse tunnel (NAT'd node, no listening TCP port)
        address=(
            "" if tunnel_mode
            else args.advertise or f"http://127.0.0.1:{args.port}"
        ),
    )

    # control-plane-requested drain (ISSUE 12 autoscale scale-down):
    # once the agent's graceful ladder finishes, deliver SIGTERM to
    # ourselves — both serving modes already translate it into a clean
    # exit 0 (graceful_shutdown is idempotent, the second call returns
    # the recorded stats), so the drained host actually frees itself
    # for the autoscaler to terminate
    def _exit_after_drain():
        import os
        import signal as _signal

        os.kill(os.getpid(), _signal.SIGTERM)

    agent.on_drain = _exit_after_drain
    if args.profile:
        with open(args.profile) as f:
            profile = ServingProfile.from_yaml(f.read())
        state = agent.apply_profile(profile)
        if state.status == "failed":
            print(f"profile apply failed: {state.error}", file=sys.stderr)
            return 1
        print(f"profile '{profile.name}' running: {state.models}")
    if args.control_plane:
        agent.start_heartbeat(poll_assignment=not args.profile)
    server = OpenAIServer(agent.registry)
    app = server.build_app()

    # expose agent state for the control plane / debugging
    async def state_handler(request):
        return web.json_response(agent.heartbeat_payload())

    app.router.add_get("/api/v1/state", state_handler)

    # graceful shutdown (ISSUE 11): SIGTERM/SIGINT (the rolling-restart
    # signals) set `draining` in the heartbeat, drain in-flight streams
    # for HELIX_DRAIN_SECONDS, export survivors to a peer runner, then
    # exit 0 — a restart no longer hard-kills client streams
    async def _graceful(_app):
        import asyncio

        await asyncio.get_running_loop().run_in_executor(
            None, agent.graceful_shutdown
        )

    app.on_shutdown.append(_graceful)

    if tunnel_mode:
        import asyncio
        import os
        import signal
        import tempfile

        from helix_tpu.control.tunnel import TunnelAgent

        sock = getattr(args, "unix_socket", None) or os.path.join(
            tempfile.mkdtemp(prefix="helix-node-"), "openai.sock"
        )

        async def main():
            runner = web.AppRunner(app)
            await runner.setup()
            await web.UnixSite(runner, sock).start()
            print(
                f"helix-tpu node on unix socket {sock}; tunnelling to "
                f"{args.control_plane}"
            )
            ta = TunnelAgent(
                args.runner_id, args.control_plane, unix_socket=sock,
                runner_token=agent.runner_token,
            )
            loop = asyncio.get_running_loop()
            stop = asyncio.Event()
            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    loop.add_signal_handler(sig, stop.set)
                except (NotImplementedError, RuntimeError):
                    pass   # non-main thread / platform without signals
            ta_task = asyncio.create_task(ta.run())
            stop_task = asyncio.create_task(stop.wait())
            await asyncio.wait(
                {ta_task, stop_task},
                return_when=asyncio.FIRST_COMPLETED,
            )
            if stop.is_set():
                print("draining before exit (SIGTERM/SIGINT)...")
                await loop.run_in_executor(None, agent.graceful_shutdown)
                ta_task.cancel()
            for t in (ta_task, stop_task):
                t.cancel()

        asyncio.run(main())
        return 0
    import signal

    from aiohttp.web_runner import GracefulExit

    def _sigterm(signum, frame):
        # run_app catches GracefulExit, runs app cleanup (our on_shutdown
        # drain hook included) and returns normally -> exit 0
        raise GracefulExit()

    try:
        signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass   # not the main thread (embedded/test use)
    print(f"helix-tpu node listening on {args.host}:{args.port}")
    web.run_app(app, host=args.host, port=args.port, print=None)
    return 0


def _cmd_serve(args) -> int:
    from aiohttp import web

    from helix_tpu.control.server import ControlPlane

    api_host = (
        "127.0.0.1"
        if args.host in ("0.0.0.0", "127.0.0.1", "localhost", "::")
        else args.host
    )
    compute_cfg = None
    if getattr(args, "compute_floor", 0) or getattr(args, "compute_max", 0):
        from helix_tpu.control.compute import ManagerConfig

        compute_cfg = ManagerConfig(
            floor=args.compute_floor,
            max=args.compute_max,
            idle_timeout=args.compute_idle_timeout,
        )
    cp = ControlPlane(
        db_path=args.db,
        sandbox_agents_url=(
            f"http://{api_host}:{args.port}"
            if getattr(args, "sandbox_agents", False)
            else None
        ),
        external_agent_argv=(
            __import__("shlex").split(args.external_agent)
            if getattr(args, "external_agent", "")
            else None
        ),
        compute_cfg=compute_cfg,
    )
    print(f"helix-tpu control plane listening on {args.host}:{args.port}")
    web.run_app(cp.build_app(), host=args.host, port=args.port, print=None)
    return 0


def _cmd_profile(args) -> int:
    from helix_tpu.control.profile import ServingProfile

    with open(args.file) as f:
        profile = ServingProfile.from_yaml(f.read())
    errors = profile.validate()
    out = {
        "name": profile.name,
        "models": profile.model_names,
        "requirement": profile.requirement.to_dict(),
        "valid": not errors,
        "errors": errors,
    }
    print(json.dumps(out, indent=2))
    return 0 if not errors else 1


def _cmd_apply(args) -> int:
    """Apply a helix.yaml app to the control plane (reference:
    ``helix apply -f helix.yaml``, ``api/pkg/cli/apps/local.go``)."""
    import requests

    with open(args.file) as f:
        raw = f.read()
    r = requests.post(
        f"{args.url}/api/v1/apps",
        data=raw,
        headers={"Content-Type": "application/x-yaml"},
        timeout=30,
    )
    if r.status_code != 200:
        print(r.text, file=sys.stderr)
        return 1
    doc = r.json()
    print(f"applied app '{doc['name']}' ({doc['id']})")
    return 0


def _api(args, method: str, path: str, **kw):
    """Authenticated control-plane call shared by the admin verbs
    (reference: the cobra CLI's API client, ``api/pkg/cli/``)."""
    import os

    import requests

    key = getattr(args, "api_key", None) or os.environ.get(
        "HELIX_API_KEY", ""
    )
    headers = kw.pop("headers", {})
    if key:
        headers["Authorization"] = f"Bearer {key}"
    r = requests.request(
        method, f"{args.url}{path}", headers=headers, timeout=60, **kw
    )
    if r.status_code >= 400:
        print(r.text, file=sys.stderr)
        raise SystemExit(1)
    return r.json()


def _cmd_org(args) -> int:
    if args.action == "create":
        doc = _api(args, "POST", "/api/v1/orgs", json={"name": args.name})
        print(f"created org {doc['id']}")
    elif args.action == "list":
        for o in _api(args, "GET", "/api/v1/orgs")["orgs"]:
            print(f"{o['id']}\t{o['name']}")
    elif args.action == "add-member":
        _api(
            args, "POST", f"/api/v1/orgs/{args.org}/members",
            json={"user_id": args.user, "role": args.role},
        )
        print(f"added {args.user} to {args.org} as {args.role}")
    elif args.action == "members":
        for m in _api(
            args, "GET", f"/api/v1/orgs/{args.org}/members"
        )["members"]:
            print(f"{m['user_id']}\t{m['role']}")
    return 0


def _cmd_knowledge(args) -> int:
    if args.action == "list":
        for k in _api(args, "GET", "/api/v1/knowledge")["knowledge"]:
            print(f"{k['id']}\t{k['state']}\tv{k['version']}\t{k['name']}")
    elif args.action == "create":
        body = {"name": args.name}
        if args.path:
            body["path"] = args.path
        if args.urls:
            body["urls"] = args.urls
            if args.crawl_depth:
                body["crawl_depth"] = args.crawl_depth
        doc = _api(args, "POST", "/api/v1/knowledge", json=body)
        print(f"created knowledge {doc['id']} ({doc['state']})")
    elif args.action == "search":
        doc = _api(
            args, "POST", f"/api/v1/knowledge/{args.id}/search",
            json={"query": args.query, "top_k": args.top_k},
        )
        for r in doc["results"]:
            print(f"[{r['score']:.3f}] {r['text'][:120]}")
    elif args.action == "refresh":
        _api(args, "POST", f"/api/v1/knowledge/{args.id}/refresh")
        print("refresh queued")
    elif args.action == "delete":
        _api(args, "DELETE", f"/api/v1/knowledge/{args.id}")
        print("deleted")
    return 0


def _cmd_operator(args) -> int:
    """K8s operator: reconcile AIApp CRs into control-plane apps
    (reference: operator/ kubebuilder controller)."""
    import os
    import time as _time

    from helix_tpu.services.k8s_operator import AIAppReconciler, K8sClient

    if args.kubeconfig_url:
        k8s = K8sClient(args.kubeconfig_url, token=args.k8s_token)
    else:
        k8s = K8sClient.in_cluster()
    rec = AIAppReconciler(
        k8s,
        helix_url=args.api or os.environ.get(
            "HELIX_API_URL", "http://localhost:8080"
        ),
        helix_token=os.environ.get("HELIX_API_TOKEN", ""),
        resync_interval=args.resync,
    ).start()
    print("operator running (ctrl-c to stop)")
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        rec.stop()
    return 0


def _cmd_evals(args) -> int:
    """Evaluation suites/runs (reference: the `evals` verb,
    api/cmd/helix/evals.go, + suite/run routes server.go:1058-1067)."""
    import json as _json

    base = f"/api/v1/apps/{args.app}"
    if args.action == "list":
        for s in _api(args, "GET", f"{base}/evaluation-suites")["suites"]:
            nq = len(s.get("questions", []))
            print(f"{s['id']}\t{nq} questions\t{s.get('name', '')}")
    elif args.action == "create":
        with open(args.file) as f:
            raw = f.read()
        try:
            doc = _json.loads(raw)
        except ValueError:
            import yaml as _yaml

            doc = _yaml.safe_load(raw)
        s = _api(args, "POST", f"{base}/evaluation-suites", json=doc)
        print(f"created suite {s['id']} ({len(s['questions'])} questions)")
    elif args.action == "run":
        run = _api(
            args, "POST", f"{base}/evaluation-suites/{args.id}/runs"
        )
        rid = run["id"]
        print(f"run {rid} started")
        import time as _time

        while True:
            run = _api(args, "GET", f"{base}/evaluation-runs/{rid}")
            if run["status"] in ("completed", "failed", "cancelled"):
                break
            _time.sleep(1.0)
        summ = run.get("summary", {})
        print(
            f"{run['status']}: {summ.get('passed', 0)}/"
            f"{summ.get('total_questions', 0)} passed"
        )
        for r in run.get("results", []):
            mark = "PASS" if r["passed"] else "FAIL"
            print(f"  [{mark}] {r['question'][:70]}")
        return 0 if run["status"] == "completed" and not summ.get(
            "failed", 0
        ) else 1
    elif args.action == "runs":
        for r in _api(
            args, "GET", f"{base}/evaluation-suites/{args.id}/runs"
        )["runs"]:
            summ = r.get("summary", {})
            print(
                f"{r['id']}\t{r['status']}\t"
                f"{summ.get('passed', 0)}/{summ.get('total_questions', 0)}"
            )
    elif args.action == "show":
        print(
            _json.dumps(
                _api(args, "GET", f"{base}/evaluation-runs/{args.id}"),
                indent=2,
            )
        )
    elif args.action == "delete":
        _api(args, "DELETE", f"{base}/evaluation-suites/{args.id}")
        print("deleted")
    return 0


def _cmd_secret(args) -> int:
    if args.action == "set":
        value = args.value
        if value is None:
            import getpass

            value = getpass.getpass(f"value for {args.name}: ")
        _api(
            args, "POST", "/api/v1/secrets",
            json={"name": args.name, "value": value},
        )
        print(f"secret {args.name} stored")
    elif args.action == "list":
        for s in _api(args, "GET", "/api/v1/secrets")["secrets"]:
            print(s["name"])
    elif args.action == "delete":
        _api(args, "DELETE", f"/api/v1/secrets/{args.name}")
        print("deleted")
    return 0


def _cmd_runner(args) -> int:
    if args.action == "list":
        for r in _api(args, "GET", "/api/v1/runners")["runners"]:
            models = ",".join(r["models"]) or "-"
            print(
                f"{r['id']}\t{r['profile_name'] or '-'}\t"
                f"{r['profile_status']}\t{models}"
            )
    elif args.action == "logs":
        doc = _api(
            args, "GET",
            f"/api/v1/runners/{args.id}/logs?tail={args.tail}",
        )
        for entry in doc["logs"]:
            print(entry["line"])
    return 0


def _cmd_config_reference(args) -> int:
    from helix_tpu.config_reference import render

    print(render())
    return 0


def _cmd_chat(args) -> int:
    import requests

    r = requests.post(
        f"{args.url}/v1/chat/completions",
        json={
            "model": args.model,
            "messages": [{"role": "user", "content": args.message}],
            "max_tokens": args.max_tokens,
            "temperature": args.temperature,
        },
        timeout=600,
    )
    if r.status_code != 200:
        print(r.text, file=sys.stderr)
        return 1
    print(r.json()["choices"][0]["message"]["content"])
    return 0


def _cmd_sft(args) -> int:
    """LoRA SFT: the `fine-tune a model from a JSONL dataset` surface the
    reference exposed through fine-tune sessions (axolotl, deleted)."""
    import dataclasses as _dc
    import json as _json

    from helix_tpu.parallel.multihost import (
        MultiHostConfig,
        host_local_slice,
        initialize,
        is_coordinator,
    )

    # join the DCN world BEFORE the first backend query (jax.devices()
    # must span every host for the global mesh)
    # per-field merge: a flag the user passed overrides env; an omitted
    # flag (None default) falls back to env.  None-sentinels matter:
    # --host-rank 0 and --num-hosts 1 are legitimate explicit values.
    env_cfg = MultiHostConfig.from_env()

    def _flag(name, env_val):
        v = getattr(args, name, None)
        return env_val if v is None else v

    mh = MultiHostConfig(
        coordinator=_flag("coordinator", env_cfg.coordinator),
        num_processes=_flag("num_hosts", env_cfg.num_processes),
        process_id=_flag("host_rank", env_cfg.process_id),
    )
    distributed = initialize(mh)

    import jax

    from helix_tpu.device.mesh import default_mesh_spec, build_mesh
    from helix_tpu.models.common import CATALOG, ModelConfig
    from helix_tpu.models.llama import init_params, param_logical_axes
    from helix_tpu.parallel.sharding import shard_params
    from helix_tpu.serving.tokenizer import load_tokenizer
    from helix_tpu.training.checkpoint import resume_trainer, save_checkpoint
    from helix_tpu.training.data import load_jsonl, pack_examples
    from helix_tpu.training.lora import LoraConfig
    from helix_tpu.training.sft import SFTConfig, SFTTrainer

    tokenizer = load_tokenizer(args.checkpoint, args.model)
    if args.checkpoint:
        from helix_tpu.models.loader import load_params

        model_cfg, params = load_params(args.checkpoint)
    else:
        model_cfg = CATALOG.get(args.model) or ModelConfig.tiny(name=args.model)
        params = init_params(model_cfg, jax.random.PRNGKey(0))

    n_dev = len(jax.devices())
    mesh = None
    if distributed:
        from helix_tpu.parallel.multihost import global_mesh_spec

        mesh = build_mesh(global_mesh_spec())
        params = shard_params(params, mesh, param_logical_axes(model_cfg))
    elif n_dev > 1:
        mesh = build_mesh(default_mesh_spec(n_dev))
        params = shard_params(params, mesh, param_logical_axes(model_cfg))

    cfg = SFTConfig(
        lora=LoraConfig(rank=args.rank, alpha=args.alpha),
        learning_rate=args.lr,
        total_steps=args.steps,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
    )
    rank0 = not distributed or is_coordinator()
    trainer = SFTTrainer(model_cfg, params, cfg, mesh=mesh)
    if args.resume and args.output:
        if resume_trainer(trainer, args.output) and rank0:
            print(f"resumed from step {trainer.step_num}")

    examples = load_jsonl(args.data, tokenizer)
    if rank0:
        print(f"loaded {len(examples)} examples")

    def batches():
        epoch = 0
        while True:
            for b in pack_examples(
                examples, cfg.batch_size, cfg.seq_len, shuffle_seed=epoch
            ):
                if distributed:
                    # every host packs the same deterministic global batch
                    # and feeds only its own rows (dp-outermost layout)
                    b = _dc.replace(b, **{
                        f.name: host_local_slice(
                            getattr(b, f.name), mh.process_id,
                            mh.num_processes,
                        )
                        for f in _dc.fields(b)
                    })
                yield b
            epoch += 1

    def on_log(m):
        if rank0:
            print(_json.dumps(m), flush=True)   # one log stream (rank 0)

    def on_step(step):
        if args.output and step % args.save_every == 0:
            # checkpoint save is a cross-process collective (every rank
            # writes its addressable shards + a sync barrier) — it MUST
            # run on all hosts, to a shared filesystem.  Fired from the
            # per-step hook so --save-every is honoured exactly, not
            # only when it happens to align with --log-every.
            save_checkpoint(
                args.output, trainer.step_num, trainer.lora_params,
                trainer.opt_state,
                lora_scaling=trainer.cfg.lora.scaling,
            )

    trainer.train(
        batches(), log_every=args.log_every, on_log=on_log, on_step=on_step
    )
    if args.output:
        # all ranks participate in the (collective) save; rank 0 narrates
        save_checkpoint(
            args.output, trainer.step_num, trainer.lora_params,
            trainer.opt_state,
            lora_scaling=trainer.cfg.lora.scaling,
        )
        if rank0:
            print(f"saved adapters to {args.output}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="helix-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    n = sub.add_parser("serve-node", help="run a TPU serving node")
    n.add_argument("--profile", help="profile YAML to apply at boot")
    n.add_argument("--runner-id", default="node-0")
    n.add_argument("--host", default="0.0.0.0")
    n.add_argument("--port", type=int, default=8000)
    n.add_argument("--control-plane", help="control plane base URL")
    n.add_argument("--heartbeat-interval", type=float, default=30.0)
    n.add_argument("--advertise", help="address the control plane dials back")
    n.add_argument(
        "--tunnel", action="store_true",
        help="no listening TCP port: serve on a unix socket and dial an "
             "outbound reverse tunnel to the control plane (NAT'd nodes)",
    )
    n.add_argument("--unix-socket", help="socket path for --tunnel mode")
    n.set_defaults(fn=_cmd_serve_node)

    s = sub.add_parser("serve", help="run the control plane")
    s.add_argument("--host", default="0.0.0.0")
    s.add_argument("--port", type=int, default=8080)
    s.add_argument("--db", default="helix.db")
    s.add_argument(
        "--sandbox-agents", action="store_true",
        help="run spec-task agents in isolated resource-limited "
             "subprocesses instead of in-process",
    )
    s.add_argument(
        "--external-agent", default="",
        help="drive a third-party ACP coding-agent CLI for spec tasks "
             "(e.g. 'claude-code-acp'); overrides --sandbox-agents",
    )
    s.add_argument(
        "--compute-floor", type=int, default=0,
        help="autoscaler: minimum provisioned hosts (stub provider "
             "unless one is wired programmatically)",
    )
    s.add_argument("--compute-max", type=int, default=0,
                   help="autoscaler: hard host ceiling (0 = floor only)")
    s.add_argument("--compute-idle-timeout", type=float, default=600.0,
                   help="autoscaler: idle seconds before shedding a host")
    s.set_defaults(fn=_cmd_serve)

    db = sub.add_parser(
        "desktop-bridge",
        help="guest agent: serve this process's GUI desktop to a "
             "control plane (runs inside a sandbox)",
    )
    db.add_argument("--control-plane", required=True)
    db.add_argument("--name", default="bridged-desktop")
    db.add_argument("--fps", type=float, default=10.0)
    db.add_argument("--api-key", default="")

    def _cmd_desktop_bridge(args):
        from helix_tpu.desktop.bridge import main as bridge_main

        argv = ["--control-plane", args.control_plane,
                "--name", args.name, "--fps", str(args.fps)]
        if args.api_key:
            argv += ["--api-key", args.api_key]
        return bridge_main(argv)

    db.set_defaults(fn=_cmd_desktop_bridge)

    ts = sub.add_parser(
        "tts-server",
        help="run the TTS sidecar (/v1/audio/speech, Klatt backend)",
    )
    ts.add_argument("--port", type=int, default=8444)

    def _cmd_tts(args):
        import asyncio as _asyncio

        from aiohttp import web as _web

        from helix_tpu.services.tts import TTSService

        async def main():
            runner = _web.AppRunner(TTSService().build_app())
            await runner.setup()
            await _web.TCPSite(runner, "0.0.0.0", args.port).start()
            print(f"tts-server on :{args.port}")
            while True:
                await _asyncio.sleep(3600)

        _asyncio.run(main())
        return 0

    ts.set_defaults(fn=_cmd_tts)

    pr = sub.add_parser("profile", help="validate a profile YAML")
    pr.add_argument("file")
    pr.set_defaults(fn=_cmd_profile)

    ap = sub.add_parser("apply", help="apply a helix.yaml app")
    ap.add_argument("-f", "--file", required=True)
    ap.add_argument("--url", default="http://127.0.0.1:8080")
    ap.set_defaults(fn=_cmd_apply)

    c = sub.add_parser("chat", help="one-shot chat against a server")
    c.add_argument("message")
    c.add_argument("--url", default="http://127.0.0.1:8000")
    c.add_argument("--model", required=True)
    c.add_argument("--max-tokens", type=int, default=256)
    c.add_argument("--temperature", type=float, default=0.0)
    c.set_defaults(fn=_cmd_chat)

    # shared --url/--api-key live on every ACTION subparser (parents=)
    # so the natural `helix org list --url ...` order works
    api_flags = argparse.ArgumentParser(add_help=False)
    api_flags.add_argument("--url", default="http://127.0.0.1:8080")
    api_flags.add_argument(
        "--api-key", help="bearer key (or HELIX_API_KEY)"
    )

    o = sub.add_parser("org", help="org administration")
    osub = o.add_subparsers(dest="action", required=True)
    oc = osub.add_parser("create", parents=[api_flags])
    oc.add_argument("name")
    osub.add_parser("list", parents=[api_flags])
    om = osub.add_parser("add-member", parents=[api_flags])
    om.add_argument("org")
    om.add_argument("user")
    om.add_argument("--role", default="member")
    ol = osub.add_parser("members", parents=[api_flags])
    ol.add_argument("org")
    o.set_defaults(fn=_cmd_org)

    k = sub.add_parser("knowledge", help="knowledge sources")
    ksub = k.add_subparsers(dest="action", required=True)
    ksub.add_parser("list", parents=[api_flags])
    kc = ksub.add_parser("create", parents=[api_flags])
    kc.add_argument("name")
    kc.add_argument("--path")
    kc.add_argument("--urls", nargs="*")
    kc.add_argument("--crawl-depth", type=int, default=0)
    ks = ksub.add_parser("search", parents=[api_flags])
    ks.add_argument("id")
    ks.add_argument("query")
    ks.add_argument("--top-k", type=int, default=5)
    kr = ksub.add_parser("refresh", parents=[api_flags])
    kr.add_argument("id")
    kd = ksub.add_parser("delete", parents=[api_flags])
    kd.add_argument("id")
    k.set_defaults(fn=_cmd_knowledge)

    op = sub.add_parser(
        "operator", help="K8s operator: reconcile AIApp CRs into apps"
    )
    op.add_argument("--api", default="", help="control plane URL")
    op.add_argument("--kubeconfig-url", default="",
                    help="K8s API URL (empty = in-cluster config)")
    op.add_argument("--k8s-token", default="")
    op.add_argument("--resync", type=float, default=30.0)
    op.set_defaults(fn=_cmd_operator)

    ev = sub.add_parser("evals", help="evaluate an app with a test suite")
    evsub = ev.add_subparsers(dest="action", required=True)
    for act, extra in (
        ("list", ()), ("create", ("file",)), ("run", ("id",)),
        ("runs", ("id",)), ("show", ("id",)), ("delete", ("id",)),
    ):
        ep = evsub.add_parser(act, parents=[api_flags])
        ep.add_argument("--app", required=True, help="app id")
        for a in extra:
            ep.add_argument(a)
    ev.set_defaults(fn=_cmd_evals)

    se = sub.add_parser("secret", help="user secrets")
    sesub = se.add_subparsers(dest="action", required=True)
    ss = sesub.add_parser("set", parents=[api_flags])
    ss.add_argument("name")
    ss.add_argument("value", nargs="?")
    sesub.add_parser("list", parents=[api_flags])
    sd = sesub.add_parser("delete", parents=[api_flags])
    sd.add_argument("name")
    se.set_defaults(fn=_cmd_secret)

    ru = sub.add_parser("runner", help="runner administration")
    rusub = ru.add_subparsers(dest="action", required=True)
    rusub.add_parser("list", parents=[api_flags])
    rl = rusub.add_parser("logs", parents=[api_flags])
    rl.add_argument("id")
    rl.add_argument("--tail", type=int, default=200)
    ru.set_defaults(fn=_cmd_runner)

    cr = sub.add_parser(
        "config-reference",
        help="print every HELIX_* environment variable the runtime reads",
    )
    cr.set_defaults(fn=_cmd_config_reference)

    t = sub.add_parser("sft", help="LoRA supervised fine-tune from JSONL")
    t.add_argument("--data", required=True, help="JSONL dataset path")
    t.add_argument("--model", default="tiny", help="catalogue model name")
    t.add_argument("--checkpoint", help="HF checkpoint dir (weights+tokenizer)")
    t.add_argument("--output", help="adapter checkpoint dir")
    t.add_argument("--resume", action="store_true")
    t.add_argument("--rank", type=int, default=16)
    t.add_argument("--alpha", type=float, default=32.0)
    t.add_argument("--lr", type=float, default=1e-4)
    t.add_argument("--steps", type=int, default=100)
    t.add_argument("--batch-size", type=int, default=8)
    t.add_argument("--seq-len", type=int, default=1024)
    t.add_argument("--save-every", type=int, default=50)
    t.add_argument("--log-every", type=int, default=10)
    t.add_argument("--coordinator", default=None,
                   help="multi-host: process 0's host:port (DCN world)")
    t.add_argument("--num-hosts", type=int, default=None)
    t.add_argument("--host-rank", type=int, default=None)
    t.set_defaults(fn=_cmd_sft)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
