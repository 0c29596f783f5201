"""Component config decode/default/validate + legacy Policy translation
(reference: pkg/scheduler/apis/config tests, legacy_registry_test.go)."""
import pytest

from kubetpu.apis import load as cfgload
from kubetpu.apis.config import KubeSchedulerConfiguration
from kubetpu.framework.runtime import Framework
from kubetpu.plugins.intree import new_in_tree_registry
from kubetpu.utils.features import FeatureGate, FeatureSpec


def test_load_config_yaml():
    doc = {
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "podInitialBackoffSeconds": 2,
        "podMaxBackoffSeconds": 20,
        "profiles": [
            {"schedulerName": "default-scheduler"},
            {"schedulerName": "no-spread",
             "plugins": {"score": {
                 "disabled": [{"name": "PodTopologySpread"}],
                 "enabled": [{"name": "NodeResourcesMostAllocated",
                              "weight": 5}]}},
             "pluginConfig": [{"name": "InterPodAffinity",
                               "args": {"hardPodAffinityWeight": 10}}]},
        ],
    }
    cfg = cfgload.load_config(doc)
    assert cfg.pod_initial_backoff_seconds == 2
    assert len(cfg.profiles) == 2
    reg = new_in_tree_registry()
    fwk = Framework(reg, cfg.profiles[1])
    names = [p.name() for p in fwk.score_plugins]
    assert "PodTopologySpread" not in names
    assert "NodeResourcesMostAllocated" in names
    assert fwk.score_weights["NodeResourcesMostAllocated"] == 5
    assert fwk.hard_pod_affinity_weight == 10
    assert ("NodeResourcesMostAllocated", 5) in fwk.tensor_scores


def test_bad_api_version_rejected():
    with pytest.raises(cfgload.ConfigError):
        cfgload.load_config({"apiVersion": "kubescheduler.config.k8s.io/v1",
                             "kind": "KubeSchedulerConfiguration"})


def test_validation_errors():
    with pytest.raises(cfgload.ConfigError, match="percentageOfNodesToScore"):
        cfgload.load_config({"percentageOfNodesToScore": 150})
    with pytest.raises(cfgload.ConfigError, match="duplicate"):
        cfgload.load_config({"profiles": [{"schedulerName": "a"},
                                          {"schedulerName": "a"}]})
    with pytest.raises(cfgload.ConfigError, match="podMaxBackoffSeconds"):
        cfgload.load_config({"podInitialBackoffSeconds": 5,
                             "podMaxBackoffSeconds": 1})


def test_defaults_applied():
    cfg = cfgload.load_config({})
    assert len(cfg.profiles) == 1
    assert cfg.profiles[0].scheduler_name == "default-scheduler"
    assert cfg.batch_size == 256


def test_policy_translation():
    policy = {
        "kind": "Policy",
        "predicates": [{"name": "PodFitsResources"},
                       {"name": "PodFitsHostPorts"}],
        "priorities": [{"name": "LeastRequestedPriority", "weight": 2},
                       {"name": "BalancedResourceAllocation", "weight": 3},
                       {"name": "InterPodAffinityPriority", "weight": 1}],
        "hardPodAffinitySymmetricWeight": 7,
    }
    cfg = cfgload.load_policy(policy)
    fwk = Framework(new_in_tree_registry(), cfg.profiles[0])
    assert fwk.tensor_filters == ("NodeResourcesFit", "NodePorts")
    assert dict(fwk.tensor_scores) == {"NodeResourcesLeastAllocated": 2,
                                       "NodeResourcesBalancedAllocation": 3,
                                       "InterPodAffinity": 1}
    assert fwk.hard_pod_affinity_weight == 7
    # DefaultBinder always present
    assert [p.name() for p in fwk.bind_plugins] == ["DefaultBinder"]


def test_policy_default_sets():
    cfg = cfgload.load_policy({"kind": "Policy"})
    fwk = Framework(new_in_tree_registry(), cfg.profiles[0])
    assert "NodeResourcesFit" in fwk.tensor_filters
    assert "InterPodAffinity" in fwk.tensor_filters
    weights = dict(fwk.tensor_scores)
    assert weights["NodePreferAvoidPods"] == 10000
    assert weights["PodTopologySpread"] == 2


def test_policy_unknown_predicate():
    with pytest.raises(cfgload.ConfigError, match="unknown predicate"):
        cfgload.load_policy({"predicates": [{"name": "Bogus"}]})


def test_feature_gates():
    fg = FeatureGate()
    assert fg.enabled("EvenPodsSpread")
    assert not fg.enabled("BalanceAttachedNodeVolumes")
    fg.set("BalanceAttachedNodeVolumes", True)
    assert fg.enabled("BalanceAttachedNodeVolumes")
    with pytest.raises(KeyError):
        fg.enabled("NoSuchGate")
    with pytest.raises(ValueError):
        fg.set("VolumeScheduling", False)   # locked to default
    fg2 = FeatureGate()
    fg2.set("AllAlpha", True)
    assert fg2.enabled("NonPreemptingPriority")   # alpha gate flips on


def test_validation_unknown_plugin():
    # VERDICT r3 #10 / framework.go:205 plugin existence — checked against
    # the MERGED registry (Scheduler construction), never at bare config
    # load where out-of-tree plugins are not yet resolvable
    doc = {"apiVersion": "kubescheduler.config.k8s.io/v1beta1",
           "profiles": [{"schedulerName": "s",
                         "plugins": {"score": {
                             "enabled": [{"name": "Bogus"}]}}}]}
    cfg = cfgload.load_config(doc)   # loads fine: registry unknown yet
    from kubetpu.plugins.intree import new_in_tree_registry
    with pytest.raises(cfgload.ConfigError, match="unknown plugin 'Bogus'"):
        cfgload.validate(cfg, registry_names=set(new_in_tree_registry()))
    # a merged registry containing the plugin passes
    names = set(new_in_tree_registry()) | {"Bogus"}
    cfgload.validate(cfg, registry_names=names)
    # the Scheduler enforces it with its actual registry
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    with pytest.raises(cfgload.ConfigError, match="unknown plugin 'Bogus'"):
        Scheduler(ClusterStore(), config=cfg)


def test_validation_bad_score_weight():
    with pytest.raises(cfgload.ConfigError, match="negative weight"):
        cfgload.load_config({
            "profiles": [{"schedulerName": "s",
                          "plugins": {"score": {"enabled": [
                              {"name": "ImageLocality",
                               "weight": -1}]}}}]})
    with pytest.raises(cfgload.ConfigError, match="integer exactness"):
        cfgload.load_config({
            "profiles": [{"schedulerName": "s",
                          "plugins": {"score": {"enabled": [
                              {"name": "ImageLocality",
                               "weight": 2 ** 24}]}}}]})


def test_validation_percentage_range():
    with pytest.raises(cfgload.ConfigError, match="percentageOfNodesToScore"):
        cfgload.load_config({"percentageOfNodesToScore": 150})


def test_validation_duplicate_plugin_and_queue_sort():
    with pytest.raises(cfgload.ConfigError, match="enabled twice"):
        cfgload.load_config({
            "profiles": [{"schedulerName": "s",
                          "plugins": {"filter": {"enabled": [
                              {"name": "NodeName"},
                              {"name": "NodeName"}]}}}]})
    # all profiles must share one queue sort (validateCommonQueueSort)
    with pytest.raises(cfgload.ConfigError, match="same queueSort"):
        cfgload.load_config({
            "profiles": [
                {"schedulerName": "a"},
                {"schedulerName": "b",
                 "plugins": {"queueSort": {
                     "enabled": [{"name": "NodeName"}],
                     "disabled": [{"name": "*"}]}}}]})


def test_validation_hard_pod_affinity_weight():
    with pytest.raises(cfgload.ConfigError,
                       match="hardPodAffinityWeight"):
        cfgload.load_config({
            "profiles": [{"schedulerName": "s",
                          "pluginConfig": [{
                              "name": "InterPodAffinity",
                              "args": {"hardPodAffinityWeight": 1000}}]}]})


def test_validation_extender_rules():
    with pytest.raises(cfgload.ConfigError, match="positive weight"):
        cfgload.load_config({"extenders": [
            {"urlPrefix": "http://x", "prioritizeVerb": "prioritize",
             "weight": 0}]})
    with pytest.raises(cfgload.ConfigError, match="one extender"):
        cfgload.load_config({"extenders": [
            {"urlPrefix": "http://x", "bindVerb": "bind"},
            {"urlPrefix": "http://y", "bindVerb": "bind"}]})


def test_kernel_backend_field_is_refused_unless_lax():
    """The auction has one kernel path: a document asking for the removed
    backend (or any other) is an error that names it, "lax" or no field
    loads, and the configuration object has no such field to set."""
    with pytest.raises(cfgload.ConfigError, match="Pallas kernel backend"):
        cfgload.load_config({"mode": "gang", "kernelBackend": "pallas"})
    with pytest.raises(cfgload.ConfigError, match="'mosaic'"):
        cfgload.load_config({"mode": "gang", "kernelBackend": "mosaic"})
    for doc in ({"mode": "gang"}, {"mode": "gang", "kernelBackend": "lax"}):
        cfg = cfgload.load_config(doc)
        assert cfg.mode == "gang"
        assert not hasattr(cfg, "kernel_backend")
